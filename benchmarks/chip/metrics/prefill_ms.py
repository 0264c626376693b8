"""prefill_ms: the mean duration of the ``engine.prefill`` spans (one
burst admission, from staging to its first tokens on the host) that
begin in the traced window, in ms."""
import program_spans


def read(run):
    prefills = program_spans.spans(run, "engine.prefill")
    if not prefills:
        return None
    return sum(e - s for _, s, e in prefills) / len(prefills) / 1e6
