"""How the benchmark holds `python -m atomo_tpu lm` (cli.cmd_lm).

An adapter is the one place that knows a loop of the program: which line of
its log is a fenced step, and where the probe goes in. It changes nothing the
loop does: `cmd_lm` builds its program through
`parallel.model_axes.build_model_axis_program`, looked up at call time, so
the adapter wraps that one function. The state it returns gets the
benchmark's seeded weights in place of the program's own initial values, and
its jitted step is called through the probe, which counts calls and looks at
the state after the first few only.
"""

from __future__ import annotations

import re

# "LM: Step: 7, Layout: dp(dp1xsp1), Loss: 10.8405, PPL: ..." is printed after
# float(metrics["loss"]) has waited for the step, so its appearance is fenced.
STEP_LINE = re.compile(r"^LM: Step: (\d+), .*?Loss: ([^,\s]+),")
# snapshots after these calls of the step: one call is one optimizer step, so
# the first gives the first gradient and the last the change over three steps
CHECK_CALLS = (1, 3)
# one call is one optimizer step: the state after the first call gives the first gradient
ONE_STEP_PER_CALL = True


def install(probe):
    import atomo_tpu.parallel.model_axes as model_axes

    real = model_axes.build_model_axis_program

    def build(*args, **kwargs):
        prog = real(*args, **kwargs)
        state = prog.state.replace(params=probe.weights(prog.state.params))
        step = prog.step

        def probed_step(state, key, tokens):
            probe.before_call(tokens)
            new_state, metrics = step(state, key, tokens)
            probe.after_call(new_state.params, metrics)
            return new_state, metrics

        return prog._replace(state=state, step=probed_step)

    model_axes.build_model_axis_program = build

    def uninstall():
        model_axes.build_model_axis_program = real

    return uninstall


def abstract_step(args, devices):
    """The step `cmd_lm` would build for the parsed `args`, on `devices`
    that are described and not attached, with its arguments as shapes: what
    rehearse.py compiles. It follows cmd_lm's own construction (MeshSpec,
    make_lm_train_step over the dp layouts) without creating any array."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from atomo_tpu.codecs import get_codec
    from atomo_tpu.mesh.spec import MeshSpec
    from atomo_tpu.models.transformer import TransformerLM
    from atomo_tpu.parallel.lm import make_lm_train_step
    from atomo_tpu.training import create_state, make_optimizer

    if args.layout != "dp":
        raise SystemExit("rehearse: only --layout dp is described here")
    n_dev = args.n_devices
    mesh = MeshSpec.from_layout("dp", n_dev, 1).build(list(devices)[:n_dev])
    cfg = dict(vocab_size=args.vocab_size, max_len=args.seq_len, width=args.width,
               depth=args.depth, num_heads=args.num_heads)
    codec = None
    if args.code.lower() != "sgd":
        codec = get_codec(args.code, svd_rank=args.svd_rank, sample=args.sample,
                          algorithm=args.svd_algo, wire_dtype=args.svd_wire,
                          quantization_level=args.quantization_level,
                          bucket_size=args.bucket_size)
    optimizer = make_optimizer(args.optimizer, lr=args.lr, momentum=args.momentum,
                               lr_shrinkage=args.lr_shrinkage,
                               shrinkage_freq=args.shrinkage_freq)
    step = make_lm_train_step(
        cfg, optimizer, mesh, codec,
        compute_dtype=jnp.bfloat16 if args.bf16 else None, aggregate=args.aggregate,
    )
    sample = jnp.zeros((1, args.seq_len), jnp.int32)
    shapes = jax.eval_shape(
        lambda k: create_state(TransformerLM(**cfg), optimizer, k, sample),
        jax.random.PRNGKey(0),
    )
    whole = NamedSharding(mesh, P())
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=whole), shapes
    )
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=whole)
    tokens = jax.ShapeDtypeStruct(
        (args.batch_size, args.seq_len), jnp.int32,
        sharding=NamedSharding(mesh, P("dp", "sp")),
    )
    return step, (state, key, tokens)
