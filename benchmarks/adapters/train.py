"""How the benchmark holds `python -m atomo_tpu train` on one device
(cli.cmd_train -> training.trainer.train_loop -> make_train_step).

`train_loop` looks `create_state` and `make_train_step` up in its own module
at call time, so the adapter wraps those two: the state gets the benchmark's
seeded weights in place of the program's own initial values, and the jitted
step (with --superstep K one dispatch of K optimizer steps under a scan) is
called through the probe. One call is a whole block: the state is visible
between blocks only, so the probe reads it after the first block, and the
block's K per-step losses come from the block's own metrics.
"""

from __future__ import annotations

import re

# "Worker: 0, Step: 8, Epoch: 0 [...], Loss: 2.3026, Time Cost: ..." is printed
# after jax.device_get(block metrics) has waited for the block, so it is fenced.
STEP_LINE = re.compile(r"^Worker: \d+, Step: (\d+), .*?Loss: ([^,\s]+),")
CHECK_CALLS = (1,)  # the first block
ONE_STEP_PER_CALL = False  # a call is a block of steps: the first gradient alone is not visible


def install(probe):
    import atomo_tpu.training.trainer as trainer

    real_create, real_make = trainer.create_state, trainer.make_train_step

    def create_state(*args, **kwargs):
        state = real_create(*args, **kwargs)
        return state.replace(params=probe.weights(state.params))

    def make_train_step(*args, **kwargs):
        step = real_make(*args, **kwargs)

        def probed_step(state, key, images, labels):
            probe.before_call((images, labels))
            new_state, metrics = step(state, key, images, labels)
            probe.after_call(new_state.params, metrics)
            return new_state, metrics

        return probed_step

    trainer.create_state, trainer.make_train_step = create_state, make_train_step

    def uninstall():
        trainer.create_state, trainer.make_train_step = real_create, real_make

    return uninstall


def abstract_step(args, devices):
    """The single-device step `train_loop` would build for the parsed `args`,
    on a device that is described and not attached, with its arguments as
    shapes: what rehearse.py compiles."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from atomo_tpu.codecs import get_codec
    from atomo_tpu.data import SPECS, canonical_name
    from atomo_tpu.models import get_model
    from atomo_tpu.training import create_state, make_optimizer
    from atomo_tpu.training.trainer import make_train_step

    if args.n_devices != 1:
        raise SystemExit("rehearse: the train adapter describes the single-device loop only")
    spec = SPECS[canonical_name(args.dataset)]
    model = get_model(args.network, spec.num_classes)
    optimizer = make_optimizer(args.optimizer, lr=args.lr, momentum=args.momentum,
                               lr_shrinkage=args.lr_shrinkage, shrinkage_freq=args.shrinkage_freq)
    codec = None
    if args.code.lower() != "sgd":
        codec = get_codec(args.code, svd_rank=args.svd_rank, sample=args.sample,
                          quantization_level=args.quantization_level, bucket_size=args.bucket_size)
    k = args.superstep or 8  # 0 is the backend's default: 8 on the TPU
    step = make_train_step(model, optimizer, codec=codec, augment=not args.no_augment,
                           compute_dtype=jnp.bfloat16 if args.bf16 else None, superstep=k)
    one = SingleDeviceSharding(list(devices)[0])
    sample = jnp.zeros((1, *spec.image_shape), jnp.float32)
    shapes = jax.eval_shape(lambda r: create_state(model, optimizer, r, sample), jax.random.PRNGKey(0))
    on = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)  # noqa: E731
    lead = (k, args.batch_size) if k > 1 else (args.batch_size,)
    return step, (
        jax.tree_util.tree_map(on, shapes),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one),
        jax.ShapeDtypeStruct((*lead, *spec.image_shape), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct(lead, jnp.int32, sharding=one),
    )
