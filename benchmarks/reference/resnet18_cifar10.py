"""Plain reference for the `resnet18-cifar10` configuration: the CIFAR ResNet-18
(BasicBlock, NHWC, train-mode BatchNorm), cross-entropy, gradients, the ATOMO
svd codec as the configuration states it, and plain SGD.

Independent of atomo_tpu: it imports nothing of the program and takes from it
neither weights nor tables. The leaves' names are the "/"-joined paths of the
program's parameter tree (flax's automatic names), which is all the two share.

The codec is a randomised unbiased estimator, and the reference draws its own
random numbers: a second implementation cannot and should not reproduce the
program's draws. So with the codec on (`--code svd` among the cell's flags) the
two trajectories differ after the first step by sampling noise, by design, and
what can be compared is (a) the first step's loss, which no draw has touched,
and (b) how far the parameters moved over the block: the norm over all leaves
together, which concentrates, and the median leaf's gap (PERF.md §2). The
estimator itself runs on the host in numpy, leaf by leaf: exact QR and SVD,
nothing to compile. With the codec off (`--code sgd`) every loss of the block
and every leaf's change are followed.

`mode`: "float32" is the reference proper, at the precision the configuration
states: float32 arrays, no precision asked of XLA. "highest" forces full
float32 multiplies. The controls: "bfloat16" is the nearest precision below
(parameters and images cast to bfloat16 on the way in, every activation and
cotangent kept in bfloat16, BatchNorm's statistics and the loss taken in
float32, as the program's own `--bf16` does). On the TPU it reads no different
from a sound run, because XLA's default precision already rounds every
operand of a convolution to bfloat16 (PERF.md §2), so "float8", the next below
(operands rounded to float8 on the way in, cotangents on the way back,
reference/float8.py), is the control that the limits are held against.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.float8 import fp8 as _fp8

BN_EPS = 1e-5
CONTROLS = ("bfloat16", "float8")  # the nearest precision below float32, and the next


def _blocks(cfg):
    """(name, c_in, planes, stride, has_shortcut) of each BasicBlock in order."""
    out, c_in, index = [], cfg["stem_planes"], 0
    for stage, (planes, count) in enumerate(zip(cfg["stage_planes"], cfg["stage_blocks"])):
        for i in range(count):
            stride = 2 if (stage > 0 and i == 0) else 1
            out.append((f"BasicBlock_{index}", c_in, planes, stride, stride != 1 or c_in != planes))
            c_in, index = planes, index + 1
    return out


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    stem = cfg["stem_planes"]
    shapes = {
        "Conv_0/kernel": (3, 3, cfg["image_channels"], stem),
        "BatchNorm_0/scale": (stem,), "BatchNorm_0/bias": (stem,),
    }
    c_last = stem
    for name, c_in, planes, _, shortcut in _blocks(cfg):
        shapes[f"{name}/Conv_0/kernel"] = (3, 3, c_in, planes)
        shapes[f"{name}/Conv_1/kernel"] = (3, 3, planes, planes)
        for bn in (0, 1):
            shapes[f"{name}/BatchNorm_{bn}/scale"] = (planes,)
            shapes[f"{name}/BatchNorm_{bn}/bias"] = (planes,)
        if shortcut:
            shapes[f"{name}/Conv_2/kernel"] = (1, 1, c_in, planes)
            shapes[f"{name}/BatchNorm_2/scale"] = (planes,)
            shapes[f"{name}/BatchNorm_2/bias"] = (planes,)
        c_last = planes
    shapes["Dense_0/kernel"] = (c_last, cfg["num_classes"])
    shapes["Dense_0/bias"] = (cfg["num_classes"],)
    return shapes


def init_params(cfg: dict, seed: int, out_shardings=None) -> dict[str, jax.Array]:
    """All leaves on the device in one jitted call from the seed, float32:
    He-normal kernels, ones and zeros for BatchNorm, zeros for the bias."""
    shapes = param_shapes(cfg)
    names = sorted(shapes)

    def make(key):
        out = {}
        for i, name in enumerate(names):
            shape = shapes[name]
            if name.endswith("/scale"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith("/bias"):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                fan_in = math.prod(shape[:-1])
                gain = 1.0 if name.startswith("Dense") else 2.0
                out[name] = math.sqrt(gain / fan_in) * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32
                )
        return out

    return jax.jit(make, out_shardings=out_shardings)(jax.random.PRNGKey(seed % (2**31 - 1)))


def _conv_at(x, kernel, stride, pad, precision, fp8=False):
    if fp8:
        x, kernel = _fp8(x), _fp8(kernel)
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision,
    )


def _batch_norm(x, scale, bias):
    """Train mode: the batch's own mean and biased variance, taken in float32
    whatever the activations' type."""
    wide = x.astype(jnp.float32)
    mean = jnp.mean(wide, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(wide), axis=(0, 1, 2)) - jnp.square(mean)
    return ((wide - mean) * jax.lax.rsqrt(var + BN_EPS) * scale + bias).astype(x.dtype)


def loss(params: dict, images, labels, cfg: dict, mode: str = "float32"):
    if mode not in ("float32", "highest", "bfloat16", "float8"):
        raise ValueError(f"unknown reference mode {mode!r}")
    precision = jax.lax.Precision.HIGHEST if mode == "highest" else None
    if mode == "bfloat16":
        params = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
        images = images.astype(jnp.bfloat16)
    fp8 = mode == "float8"
    conv = functools.partial(_conv_at, precision=precision, fp8=fp8)
    bn = lambda x, p: _batch_norm(x, params[p + "/scale"], params[p + "/bias"])  # noqa: E731
    x = jax.nn.relu(bn(conv(images, params["Conv_0/kernel"], 1, 1), "BatchNorm_0"))
    for name, _, _, stride, shortcut in _blocks(cfg):
        out = conv(x, params[f"{name}/Conv_0/kernel"], stride, 1)
        out = jax.nn.relu(bn(out, f"{name}/BatchNorm_0"))
        out = bn(conv(out, params[f"{name}/Conv_1/kernel"], 1, 1), f"{name}/BatchNorm_1")
        if shortcut:
            x = bn(conv(x, params[f"{name}/Conv_2/kernel"], stride, 0), f"{name}/BatchNorm_2")
        x = jax.nn.relu(out + x)
    pooled = jnp.mean(x, axis=(1, 2))
    head = params["Dense_0/kernel"]
    if fp8:
        pooled, head = _fp8(pooled), _fp8(head)
    logits = jnp.matmul(pooled, head, precision=precision) + params["Dense_0/bias"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


# ---- the codec, on the host ------------------------------------------------------

def square_dims(total: int, cap: int) -> tuple[int, int]:
    if total <= 1:
        return 1, 1
    low = 1 << int(math.floor(math.log2(math.sqrt(total))))
    m = min((min(low, cap), min(2 * low, cap)), key=lambda c: c + -(-total // c))
    return m, -(-total // m)


def payload_atoms(m: int, n: int, codec: dict, rank: int) -> tuple[int, bool]:
    """How many atoms a leaf's message holds, and whether it is sketched."""
    sketched = min(m, n) >= codec["sketch_min_dim"]
    return min(rank, min(m, n)) + (codec["residual_probes"] if sketched else 0), sketched


def message_bytes(cfg: dict) -> int:
    """The encoded message of one step, from shapes: float32 factors and
    coefficients, or the dense leaf where the factors would not be smaller."""
    total_bytes = 0
    for shape in param_shapes(cfg).values():
        size = math.prod(shape)
        m, n = square_dims(size, cfg["codec"]["max_min_dim"])
        k, _ = payload_atoms(m, n, cfg["codec"], cfg["svd_rank"])
        dense = k * (m + n + 1) >= size
        total_bytes += 4 * (size if dense else k * (m + n + 1))
    return total_bytes


def atomo_estimate(grad: np.ndarray, cfg: dict, rng: np.random.Generator) -> np.ndarray:
    """One draw of the configured estimator of `grad`; its mean is `grad`."""
    codec, rank = cfg["codec"], cfg["svd_rank"]
    size = grad.size
    m, n = square_dims(size, codec["max_min_dim"])
    k_total, sketched = payload_atoms(m, n, codec, rank)
    if k_total * (m + n + 1) >= size:
        return grad
    mat = np.zeros(m * n, np.float64)
    mat[:size] = grad.reshape(-1)
    mat = mat.reshape(m, n)
    if sketched:
        width = min(rank + codec["oversample"], min(m, n))
        q, _ = np.linalg.qr(mat @ rng.standard_normal((n, width)))
        for _ in range(codec["power_iters"]):
            z, _ = np.linalg.qr(mat.T @ q)
            q, _ = np.linalg.qr(mat @ z)
        ub, s, vt = np.linalg.svd(q.T @ mat, full_matrices=False)
        u = q @ ub
    else:
        u, s, vt = np.linalg.svd(mat, full_matrices=False)
    k = min(rank, len(s))
    total = s.sum()
    if total <= 0:
        return np.zeros_like(grad)
    picks = rng.choice(len(s), size=k, replace=True, p=s / total)
    # atom i is drawn with probability s_i / total and weighs s_i / (k q_i) = total / k
    estimate = (total / k) * (u[:, picks] @ vt[picks, :])
    if sketched:
        probes = rng.choice([-1.0, 1.0], size=(n, codec["residual_probes"]))
        seen = mat @ probes
        residual = seen - u @ (u.T @ seen)  # what the sketch's subspace misses
        estimate += (residual @ probes.T) / codec["residual_probes"]
    return estimate.reshape(-1)[:size].reshape(grad.shape).astype(np.float32)


# ---- training ---------------------------------------------------------------------

def leaf_norms(tree: dict) -> dict[str, float]:
    return {k: float(np.sqrt(np.sum(np.square(np.asarray(v, np.float64))))) for k, v in tree.items()}


def train_steps(params: dict, batches, cfg: dict, mode: str = "float32", flags: dict | None = None,
                draws: int = 0):
    """Follow the optimizer steps of `batches`, each a block (images
    (K, B, H, W, C), labels (K, B)) as the superstep loop feeds them (or one
    step's (B, ...) arrays). Plain SGD, as momentum 0 leaves it. `flags` are
    the cell's flags of the train command: `--code` says whether the codec is
    on (without flags it is, at the configuration's rank). `draws` picks
    another stream of the codec's random numbers: the control and the faults,
    put in the program's place, draw apart from the reference as the program does."""
    if cfg["momentum"]:
        raise ValueError("the recipe's momentum is 0; this reference keeps no trace")
    code = (flags or {}).get("--code", "svd")
    if code not in ("svd", "sgd"):
        raise ValueError(f"this reference follows --code svd and sgd, not {code!r}")
    grad_fn = jax.jit(functools.partial(jax.value_and_grad(loss), cfg=cfg, mode=mode))
    rng = np.random.default_rng(20180101 + draws)
    coded = code == "svd"
    start = {k: np.asarray(v) for k, v in params.items()}
    now = dict(start)
    losses, grad1 = [], None
    for images, labels in batches:
        images, labels = np.asarray(images), np.asarray(labels)
        if labels.ndim == 1:
            images, labels = images[None], labels[None]
        for step_images, step_labels in zip(images, labels):
            value, grads = grad_fn(now, step_images, step_labels)
            grads = {k: np.asarray(v, np.float32) for k, v in grads.items()}
            losses.append(float(value))
            if grad1 is None:
                grad1 = leaf_norms(grads)
            if coded:
                grads = {k: atomo_estimate(g, cfg, rng) for k, g in grads.items()}
            now = {k: now[k] - np.float32(cfg["lr"]) * grads[k] for k in now}
    return {
        "losses": losses,
        # with the codec on, only the first loss comes before any draw
        "losses_followed": 1 if coded else len(losses),
        "grad1_norms": grad1,
        "change_norms": leaf_norms({k: now[k] - start[k] for k in now}),
        "change_stat": "total" if coded else "worst_leaf",
        "msg_bytes": message_bytes(cfg) if coded else None,
    }


def example_batches(cfg: dict, seed: int, calls: int, rows: int, steps: int = 2):
    """Blocks of the kind the train command feeds with --synthetic: class
    blobs plus noise, normalised. For tests and for reading the control where
    no program ran."""
    rng = np.random.default_rng(seed)
    size, channels, classes = cfg["image_size"], cfg["image_channels"], cfg["num_classes"]
    prototypes = rng.random((classes, size, size, channels)).astype(np.float32)
    out = []
    for _ in range(calls):
        labels = rng.integers(0, classes, size=(steps, rows)).astype(np.int32)
        noise = 0.15 * rng.standard_normal((steps, rows, size, size, channels)).astype(np.float32)
        images = (np.clip(prototypes[labels] + noise, 0.0, 1.0) - 0.5) / 0.25
        out.append((images.astype(np.float32), labels))
    return out
