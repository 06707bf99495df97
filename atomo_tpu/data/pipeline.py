"""Input pipeline: host-side batching + device-side jit augmentation.

Reference parity: the CIFAR train transform is pad-4 reflect -> random crop
32 -> random horizontal flip -> normalize (src/distributed_nn.py:104-120);
MNIST/SVHN use normalize(-ish) only. The reference runs these per-sample in
Python worker processes (the vendored DataLoader fork,
src/data_loader_ops/my_data_loader.py). TPU-first redesign: augmentation is
a pure vmapped jnp function executed *on device inside the compiled step* —
no Python-loop per-sample work, no multiprocess reorder queues; the host
only shuffles indices and slices batches.
"""

from __future__ import annotations

from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from atomo_tpu.data.datasets import ArrayDataset
from atomo_tpu.utils.tracing import PUT, STACK, span


def normalize(images: jax.Array, mean, std) -> jax.Array:
    mean = jnp.asarray(mean, jnp.float32)
    std = jnp.asarray(std, jnp.float32)
    return (images - mean) / std


def augment_batch(key: jax.Array, images: jax.Array, pad: int = 4) -> jax.Array:
    """Pad-reflect -> per-image random crop -> random horizontal flip.

    Pure, static-shape, vmapped: runs on the TPU inside the train step.
    """
    n, h, w, _ = images.shape
    kc, kf = jax.random.split(key)
    padded = jnp.pad(
        images, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="reflect"
    )
    offsets = jax.random.randint(kc, (n, 2), 0, 2 * pad + 1)
    flips = jax.random.bernoulli(kf, 0.5, (n,))

    def crop_one(img, off, flip):
        out = jax.lax.dynamic_slice(
            img, (off[0], off[1], 0), (h, w, img.shape[-1])
        )
        return jnp.where(flip, out[:, ::-1, :], out)

    return jax.vmap(crop_one)(padded, offsets, flips)


class BatchIterator:
    """Epoch-shuffled batch stream over an in-memory dataset.

    Replaces the reference's vendored multiprocess DataLoader
    (my_data_loader.py:310-319, incl. its persistent `next_batch`): with
    device-side augmentation the host work is an index shuffle + gather,
    which numpy does faster than a worker pool for these dataset sizes.
    """

    def __init__(
        self,
        dataset: ArrayDataset,
        batch_size: int,
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = True,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        self._rng = np.random.RandomState(seed)
        self.images = dataset.normalized()
        self.labels = dataset.labels

    def __len__(self) -> int:
        n = len(self.dataset)
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def _epoch_sels(self) -> Iterator[np.ndarray]:
        """One epoch's batch index selections (the shuffle happens here)."""
        n = len(self.dataset)
        idx = np.arange(n)
        if self.shuffle:
            self._rng.shuffle(idx)
        stop = (n // self.batch_size) * self.batch_size if self.drop_last else n
        for s in range(0, stop, self.batch_size):
            yield idx[s : s + self.batch_size]

    def epoch(self) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        for sel in self._epoch_sels():
            yield self.images[sel], self.labels[sel]

    def forever(self, skip: int = 0) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        """Endless epoch stream. ``skip`` discards that many leading
        batches WITHOUT materializing them (index-stream only) while
        consuming the exact same shuffle-RNG draws — the resume-replay
        path: a restarted run's batch sequence lines up with the
        uninterrupted run's at a cost of one index shuffle per skipped
        epoch, not a data copy per skipped batch."""
        while True:
            for sel in self._epoch_sels():
                if skip > 0:
                    skip -= 1
                    continue
                yield self.images[sel], self.labels[sel]

    def snapshot_rng(self):
        """Capture the shuffle-RNG state. Take it immediately BEFORE the
        first :meth:`forever` call and hand it to :meth:`restream` — the
        in-process rollback-replay contract (see restream)."""
        return self._rng.get_state()

    def rng_signature(self) -> int:
        """CRC32 fingerprint of the current shuffle-RNG state — the
        membership layer's JSON-able stand-in for persisting the full
        :meth:`snapshot_rng` tuple. Two streams built from the same seed
        with the same consumption history fingerprint identically, so a
        membership epoch record can PROVE its data-shard map derivation
        ("this stream, skipped N batches, split world-size ways") instead
        of asserting it. Take it at the same point as snapshot_rng
        (before :meth:`forever` advances the state)."""
        import zlib

        kind, keys, pos, has_gauss, cached = self._rng.get_state()
        h = zlib.crc32(f"{kind}:{pos}:{has_gauss}".encode())
        return zlib.crc32(np.asarray(keys).tobytes(), h)

    def restream(self, rng_state, skip: int = 0):
        """Fresh replay stream for an IN-PROCESS rollback: restore the
        shuffle RNG to ``rng_state`` (the :meth:`snapshot_rng` taken when
        the original stream was created) and skip ``skip`` batches.
        ``forever`` draws epoch shuffles from the live RNG, so simply
        calling it again mid-run would shuffle from an already-advanced
        state and hand the rolled-back run a batch sequence no fresh
        resume would ever see; restoring the snapshot makes the replay
        bit-identical to a restarted process's ``forever(skip=...)``."""
        self._rng.set_state(rng_state)
        return self.forever(skip=skip)


class BlockStream:
    """Stack consecutive batches of an endless stream into ``(K, batch,
    ...)`` superstep blocks.

    The batch sequence is exactly the underlying stream's — step t of a
    K-block is the same array a per-step loop would have fed at step t —
    so superstep runs replay (and resume) bit-identically against K=1
    runs. ``take(k)`` accepts a different ``k`` each call: the train loops
    shrink the final block to ``max_steps`` instead of overrunning it.
    """

    def __init__(self, stream: Iterator[tuple[np.ndarray, np.ndarray]]):
        self._stream = stream

    def take(self, k: int) -> tuple[np.ndarray, np.ndarray]:
        pairs = [next(self._stream) for _ in range(k)]
        return (
            np.stack([p[0] for p in pairs]),
            np.stack([p[1] for p in pairs]),
        )


class SuperstepFeed:
    """One-block device lookahead over a :class:`BlockStream`.

    ``start(k)`` stacks the next k batches and hands them to ``put_fn``
    (``jax.device_put`` / ``shard_superbatch``) immediately; jax transfers
    are asynchronous, so when the train loop calls ``start`` right after
    dispatching a superstep, the NEXT block's host->device copy overlaps
    the current block's compute — the double-buffering half of the
    superstep design (the other half is the fused scan itself). ``take()``
    returns the block ``start`` staged, as ``(k, device_images,
    device_labels)``."""

    def __init__(self, blocks: BlockStream, put_fn):
        self._blocks = blocks
        self._put = put_fn
        self._staged = None

    def start(self, k: int) -> None:
        if k > 0:
            with span(STACK):
                im, lb = self._blocks.take(k)
            with span(PUT):  # enqueues; the runtime's threads change the layout behind it
                dev_im, dev_lb = self._put(im, lb)
            self._staged = (k, dev_im, dev_lb)

    def take(self):
        staged, self._staged = self._staged, None
        return staged
