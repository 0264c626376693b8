from .cache import enable_compile_cache
from .mesh import batch_axes, make_debug_mesh, make_mesh, make_production_mesh
from .sharding import (batch_specs, cache_specs, opt_state_specs,
                       param_specs, to_shardings)
from .steps import (cache_spec_struct, input_specs, make_prefill_step,
                    make_serve_step, make_step, make_train_step, options_for,
                    params_spec_struct)

__all__ = ["enable_compile_cache", "batch_axes", "make_debug_mesh",
           "make_mesh", "make_production_mesh",
           "batch_specs", "cache_specs", "opt_state_specs", "param_specs",
           "to_shardings", "cache_spec_struct", "input_specs",
           "make_prefill_step", "make_serve_step", "make_step",
           "make_train_step", "options_for", "params_spec_struct"]
