"""Mesh description layer — ONE grammar for every device layout.

Every program family in the repo runs over a ``jax.sharding.Mesh`` whose
shape used to be re-derived ad hoc at each call site (``make_mesh(n)``
here, ``make_mesh(n, axes=(("dp", k), ("ici", n // k)))`` there, a bare
``n_devices`` int in the tune decision). :class:`MeshSpec` is the single
description those sites now share:

  * ``dp`` is always the first (outer, slow-fabric) data axis;
  * ``--dcn-ways K`` declares a SECOND data axis ``ici`` (the fast
    fabric): the mesh is ``(dp=K, ici=n/K)`` and the data-parallel world
    is the product;
  * the degenerate shapes are first-class, not special cases: a 1-device
    mesh is ``dp1`` and a flat data-parallel mesh is ``dpN`` — the same
    spec grammar, the same compile path
    (:func:`atomo_tpu.parallel.compile.compile_step`), the same artifact
    record.

``shape_dict()`` is the artifact form (``{"dp": 2, "ici": 2}``) — the
tune decision's ``meta.mesh_axes`` and the elastic membership record both
carry it, and :func:`atomo_tpu.tuning.autopilot.decision_reusable`
compares it on resume (an ``n_devices``-only check cannot tell ``dp4``
from ``dp2 x ici2``, which are different program families).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax

#: Model axes the layout grammar understands, in the order they appear in
#: a layout name. These shard the MODEL (or the sequence), not the batch
#: replicas: gradients are completed ACROSS them (psum / pmean) before the
#: data-parallel exchange, so the compressed dp wire never sees them.
MODEL_AXES = ("tp", "pp", "ep", "sp")

#: The LM layout grammar (cli ``lm --layout``): layout name -> the model
#: axes it adds after ``dp``. ``dp-tp-sp`` is the 3-D Megatron x ring
#: composition; everything else is 2-D.
LAYOUT_MODEL_AXES = {
    "dp": (),
    "dp-sp": ("sp",),
    "dp-tp": ("tp",),
    "dp-ep": ("ep",),
    "dp-pp": ("pp",),
    "dp-tp-sp": ("tp", "sp"),
}


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """An ordered tuple of named mesh axes, e.g. ``(("dp", 2), ("ici", 2))``.

    Immutable and hashable so it can ride static closures and dict keys;
    build the runtime ``jax.sharding.Mesh`` with :meth:`build`.
    """

    axes: tuple[tuple[str, int], ...]

    def __post_init__(self):
        if not self.axes:
            raise ValueError("MeshSpec needs at least one axis")
        names = [a for a, _ in self.axes]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate mesh axis names: {names}")
        for name, size in self.axes:
            if size < 1:
                raise ValueError(f"mesh axis {name!r} has size {size}")

    # ----------------------------------------------------------- builders
    @classmethod
    def from_world(cls, n_devices: int, dcn_ways: int = 0) -> "MeshSpec":
        """The ONE resolution of (--n-devices, --dcn-ways) to a mesh shape.

        ``dcn_ways`` <= 1 is the flat (or degenerate 1-device) data-parallel
        mesh ``dpN``; ``dcn_ways`` > 1 is the two-tier ``dpK x ici(N/K)``
        mesh the hierarchical schedules run on. The divisibility contract
        matches the CLI preflight: K must divide N.
        """
        n = int(n_devices)
        k = int(dcn_ways)
        if n < 1:
            raise ValueError(f"n_devices must be >= 1, got {n}")
        if k > 1:
            if n % k or not 1 < k <= n:
                raise ValueError(
                    f"dcn_ways {k} must divide n_devices {n} "
                    "(outer slow-fabric groups x inner fast-fabric chips)"
                )
            return cls((("dp", k), ("ici", n // k)))
        return cls((("dp", n),))

    @classmethod
    def from_layout(
        cls, layout: str, n_devices: int, ways=1
    ) -> "MeshSpec":
        """The ONE resolution of (``--layout``, ``--ways``) to a mesh shape
        — the LM model-axis counterpart of :meth:`from_world`.

        Reproduces exactly the axes tuples ``cli.cmd_lm`` used to hand
        ``make_mesh`` (same axes -> same mesh -> same compiled program):
        ``dp`` is ``(dp=N, sp=1)`` (the dp x sp step with a degenerate
        sequence axis — same program text, degenerate shape), the 2-D
        layouts are ``(dp=N/ways, <axis>=ways)``, and ``dp-tp-sp`` takes
        ``ways`` as a ``(tp, sp)`` pair. Divisibility mirrors the CLI
        preflight: the model ways must divide the device count.
        """
        if layout not in LAYOUT_MODEL_AXES:
            raise ValueError(
                f"unknown layout {layout!r}; expected one of "
                f"{sorted(LAYOUT_MODEL_AXES)}"
            )
        n = int(n_devices)
        if n < 1:
            raise ValueError(f"n_devices must be >= 1, got {n}")
        model = LAYOUT_MODEL_AXES[layout]
        if layout == "dp-tp-sp":
            try:
                tp_ways, sp_ways = (int(w) for w in ways)
            except TypeError:
                raise ValueError(
                    "layout 'dp-tp-sp' takes ways as a (tp, sp) pair"
                ) from None
            sizes = (tp_ways, sp_ways)
        else:
            sizes = (int(ways),) * len(model)
        m = 1
        for s in sizes:
            if s < 1:
                raise ValueError(f"model ways must be >= 1, got {s}")
            m *= s
        if n % m:
            raise ValueError(
                f"model ways {m} (layout {layout!r}) does not divide "
                f"{n} devices"
            )
        if layout == "dp":
            # cmd_lm's dp layout runs the dp x sp program with sp=1 —
            # keep the axes tuple identical so the program family is too
            return cls((("dp", n), ("sp", 1)))
        return cls(
            (("dp", n // m),) + tuple(zip(model, sizes))
        )

    @classmethod
    def from_shape_dict(cls, d) -> Optional["MeshSpec"]:
        """Inverse of :meth:`shape_dict` for artifact round-trips.

        Axis order in the artifact dict is meaningful (dp is outer);
        returns None for a missing/empty/garbage document rather than
        raising — resume code treats that as "old artifact, shape
        unrecorded" and falls back to the n_devices check.
        """
        if not isinstance(d, dict) or not d:
            return None
        try:
            axes = tuple((str(k), int(v)) for k, v in d.items())
            return cls(axes)
        except (TypeError, ValueError):
            return None

    # ---------------------------------------------------------- properties
    @property
    def names(self) -> tuple[str, ...]:
        return tuple(a for a, _ in self.axes)

    @property
    def n_devices(self) -> int:
        n = 1
        for _, s in self.axes:
            n *= s
        return n

    @property
    def data_axes(self) -> tuple[str, ...]:
        """The axes the batch (and the sharded update) spans: ``("dp",)``
        flat, ``("dp", "ici")`` two-tier."""
        return tuple(n for n in self.names if n in ("dp", "ici"))

    @property
    def model_axes(self) -> tuple[tuple[str, int], ...]:
        """The non-data (model/sequence) axes with their sizes, in mesh
        order — empty for the pure data-parallel shapes. Degenerate
        size-1 model axes are included (they are part of the program
        family: ``dp4 x sp1`` and ``dp4`` lower differently)."""
        return tuple(
            (n, s) for n, s in self.axes if n not in ("dp", "ici")
        )

    @property
    def inner_axis(self) -> Optional[str]:
        return "ici" if "ici" in self.names else None

    @property
    def is_two_tier(self) -> bool:
        return self.inner_axis is not None

    @property
    def is_degenerate(self) -> bool:
        """One device: every collective is the identity and the sharded
        update's slice is the whole vector — same program text, degenerate
        shape."""
        return self.n_devices == 1

    @property
    def is_flat(self) -> bool:
        return not self.is_two_tier

    # ----------------------------------------------------------- renderers
    def shape_dict(self) -> dict:
        """Artifact form: insertion-ordered ``{"dp": K, "ici": M}``."""
        return {name: size for name, size in self.axes}

    def describe(self) -> str:
        """Human grammar: ``dp4``, ``dp2xici2`` — the string log lines
        print."""
        return "x".join(f"{n}{s}" for n, s in self.axes)

    def layout_name(self) -> str:
        """The ``--layout`` string this shape answers to: the inverse of
        :meth:`from_layout` up to degenerate model axes (``dp4 x sp1``
        renders as ``dp`` — that IS the layout the CLI built it from).
        Raises for shapes outside the LM layout grammar (an ``ici``
        two-tier mesh is a data layout, not a model layout)."""
        live = tuple(n for n, s in self.model_axes if s > 1)
        name = "-".join(("dp",) + live)
        if "ici" in self.names or name not in LAYOUT_MODEL_AXES:
            raise ValueError(
                f"mesh shape {self.describe()} is not an LM model-axis "
                f"layout (grammar: {sorted(LAYOUT_MODEL_AXES)})"
            )
        return name

    def build(self, devices: Optional[Sequence["jax.Device"]] = None):
        """Materialize the ``jax.sharding.Mesh`` (first ``n_devices`` of
        the roster by default)."""
        from atomo_tpu.parallel.mesh import make_mesh

        return make_mesh(self.n_devices, axes=self.axes, devices=devices)


def spec_of_mesh(mesh) -> MeshSpec:
    """Recover the spec of an existing ``jax.sharding.Mesh`` (axis order
    preserved) — the bridge for call sites that still hand a raw Mesh
    around."""
    return MeshSpec(
        tuple((str(n), int(mesh.shape[n])) for n in mesh.axis_names)
    )
