"""Parallelism layer: meshes, replicated compressed-DP, distributed init."""

from atomo_tpu.parallel.mesh import (  # noqa: F401
    batch_sharded,
    make_mesh,
    replicated,
)
from atomo_tpu.parallel.compile import (  # noqa: F401
    compile_global,
    compile_step,
    shardings_from_specs,
)
from atomo_tpu.parallel.launch import (  # noqa: F401
    HealthMonitor,
    HealthWatchdog,
    global_mesh,
    initialize,
)
from atomo_tpu.parallel.replicated import (  # noqa: F401
    DelayedState,
    EfState,
    OverlapCarry,
    distributed_train_loop,
    init_delayed_state,
    init_ef_state,
    make_delayed_oracle_steps,
    make_distributed_eval_step,
    make_distributed_train_step,
    replicate_state,
    shard_batch,
    shard_superbatch,
)
from atomo_tpu.parallel.tp import (  # noqa: F401
    create_tp_lm_state,
    make_tp_lm_train_step,
    make_tp_sp_lm_train_step,
    shard_tp_tokens,
)
from atomo_tpu.parallel.moe import (  # noqa: F401
    create_moe_lm_state,
    make_moe_lm_train_step,
    shard_moe_tokens,
)
from atomo_tpu.parallel.pp import (  # noqa: F401
    create_pp_lm_state,
    make_pp_lm_train_step,
    shard_pp_tokens,
)
