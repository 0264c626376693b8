"""out_tok_s: every output token stamped inside the window, prefill's
first tokens included, over the window's seconds."""
import stats


def read(run):
    n = stats.window_tokens(run)
    return n / run.seconds if n else None
