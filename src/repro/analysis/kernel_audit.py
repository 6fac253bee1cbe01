"""Static Pallas kernel legality & VMEM auditor.

The kernels in ``src/repro/kernels/`` are only as portable as their block
configs: a tile that violates Mosaic's sublane/lane layout rules, an
``index_map`` that walks off the padded operand, or a working set that
does not fit the per-core VMEM budget all fail *at lowering time* on a
real TPU — long after the autotuner cache or a caller picked the config.
This module proves those properties ahead of time, with zero FLOPs:

* **closed-form layer** — :func:`validate_blocks` / :func:`vmem_bytes`
  score a ``{"bm": .., "bn": .., "bk": ..}``-style block dict against a
  per-kind model of every tile the kernel streams (operands, outputs,
  scratch).  This is what ``kernels/autotune.py`` uses to refuse illegal
  candidates/cache rows and what the wrappers call (via
  :func:`check_wrapper_blocks`) to fail fast with the kernel, blocks,
  and computed VMEM bytes in the message;
* **capture layer** — :func:`capture_launches` abstract-interprets a
  wrapper under ``jax.eval_shape`` with ``pl.pallas_call`` shimmed out,
  recording every launch's grid, BlockSpecs, operand/output avals and
  scratch shapes; :func:`check_launch` then verifies tiling legality,
  grid x index_map coverage (no out-of-bounds block reads, every output
  tile written), the VMEM working set, and the fused kernels'
  digit-axis scratch residency against the *actual* traced launch;
* **report layer** — :func:`audit_all` sweeps every kernel family x
  shape bucket x block config (defaults, every autotune CANDIDATE, and
  any persisted cache row) and returns a :class:`KernelAuditReport`;
  :func:`audit_engine_kernels` audits the launches of a built engine's
  own ``_trace_specs()`` closures (the gate behind
  ``ServeConfig(audit=True)``).

VMEM accounting (the formula ``docs/analysis.md`` documents)::

    working_set = 2 * sum(block_bytes(operand and output tiles))
                + sum(scratch_bytes)           <= 16 MiB per core

The factor 2 models Mosaic's double-buffering of every streamed block
(next tile prefetches while the current one computes); scratch is
allocated once per core and is not double-buffered.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import json
import threading

__all__ = [
    "BUDGET_BYTES",
    "LANE",
    "BlockConfigError",
    "KernelLaunch",
    "KernelAuditReport",
    "audit_all",
    "audit_config",
    "audit_engine_kernels",
    "capture_launches",
    "check_launch",
    "check_wrapper_blocks",
    "sublane",
    "validate_blocks",
    "vmem_bytes",
]

#: per-core VMEM budget the working set must fit in (16 MiB).
BUDGET_BYTES = 16 * 2**20

#: lane count — the last dim of every >=1-D tile lays out over 128 lanes.
LANE = 128

#: minimum sublane multiple by element width: (8, 128) f32/int32 tiles,
#: (16, 128) for 2-byte, (32, 128) for int8.
_SUBLANE = {1: 32, 2: 16, 4: 8}

#: grids larger than this are corner-sampled instead of enumerated.
_MAX_ENUM = 65536

_CAPTURE_LOCK = threading.Lock()


class BlockConfigError(ValueError):
    """An illegal (Mosaic-illegal or VMEM-over-budget) block config,
    raised by the wrapper-side gate.  A distinct type so the OTHER
    auditors tracing the same wrappers (the exactness auditor runs them
    under ``eval_shape`` too) can tell a tile-legality refusal apart
    from a numeric ledger error and blame the right pass."""

_MATMUL_KINDS = (
    "rns_matmul",
    "rns_fused_encode_matmul",
    "rns_fused_matmul_normalize",
    "rns_fused_dot",
)

#: block names each kind requires (the autotune DEFAULTS schema).
_REQUIRED: dict[str, tuple[str, ...]] = {
    **{k: ("bm", "bn", "bk") for k in _MATMUL_KINDS},
    "rns_convert": ("bt",),
    "rns_normalize": ("bt",),
    "flash_attention": ("bq", "bk"),
}

#: (package under kernels/, kernel fn __name__) -> audit kind.
_KIND_BY_FN = {
    ("rns_matmul", "_kernel"): "rns_matmul",
    ("rns_convert", "_kernel"): "rns_convert",
    ("rns_normalize", "_kernel"): "rns_normalize",
    ("rns_fused", "_encode_matmul_kernel"): "rns_fused_encode_matmul",
    ("rns_fused", "_matmul_normalize_kernel"): "rns_fused_matmul_normalize",
    ("rns_fused", "_fused_dot_kernel"): "rns_fused_dot",
    ("flash_attention", "_kernel"): "flash_attention",
}

#: fused kinds whose digit axis must stay scratch-resident:
#: kind -> index of the weight-residue operand whose leading dim is K.
_RESIDENT_B_OPERAND = {
    "rns_fused_matmul_normalize": 1,
    "rns_fused_dot": 2,
}

#: audited shape families per kind (pre-padding wrapper shapes).
#: matmul kinds: (M, D, N); convert/normalize: (T,);
#: flash: (B, Tq, Tk, H, Hk, D, Dv).
_AUDIT_SHAPES: dict[str, list[tuple[int, ...]]] = {
    **{k: [(8, 512, 512), (128, 2048, 2048)] for k in _MATMUL_KINDS},
    "rns_convert": [(512,), (65536,)],
    "rns_normalize": [(512,), (65536,)],
    "flash_attention": [(1, 128, 128, 4, 4, 64, 64),
                        (2, 256, 512, 8, 4, 64, 64)],
}


def sublane(elem_bytes: int) -> int:
    """Minimum sublane multiple for an element width in bytes."""
    return _SUBLANE.get(int(elem_bytes), 8)


# ---------------------------------------------------------------------------
# closed-form layer: block dict -> tile model -> violations / VMEM bytes
# ---------------------------------------------------------------------------


def _tile_violations(label, block, elem_bytes, full):
    """Mosaic tiling legality for one tile.

    Per dim: a known array dim must be evenly tiled; the lane (last) dim
    must be a LANE multiple unless the block covers the whole dim; the
    sublane (2nd-last) dim must be a sublane(dtype) multiple unless it
    covers the whole dim (a 1 there is refused too: v5e's compiler
    rejected a ``(1, 1)`` block over a ``(K, 1)`` array).  Leading dims
    only need to divide.
    """
    out = []
    nd = len(block)
    sub = sublane(elem_bytes)
    for axis, b in enumerate(block):
        f = None
        if full is not None and axis < len(full):
            f = full[axis]
        if not isinstance(b, int) or isinstance(b, bool) or b <= 0:
            out.append(f"{label}: block dim {axis} is {b!r} "
                       "(need a positive int)")
            continue
        whole = f is not None and b == f
        if f is not None:
            if b > f:
                out.append(f"{label}: block dim {axis} = {b} exceeds "
                           f"array dim {f}")
            elif f % b != 0:
                out.append(f"{label}: block dim {axis} = {b} does not "
                           f"evenly tile array dim {f}")
        if axis == nd - 1:
            if b % LANE != 0 and not whole:
                out.append(f"{label}: lane dim {b} is not a multiple of "
                           f"{LANE} (and does not span the array dim)")
        elif axis == nd - 2:
            if b % sub != 0 and not whole:
                out.append(f"{label}: sublane dim {b} is not a multiple "
                           f"of {sub} for {elem_bytes}-byte elements")
    return out


def _block_layout(kind, blocks, n_digits, res_bytes, dims):
    """The per-kind tile model: every VMEM block a launch streams.

    Returns ``(tiles, scratch)`` where tiles are
    ``(label, block_shape, elem_bytes, full_dims_or_None)`` and scratch
    entries are ``(shape, elem_bytes)``.  ``dims`` names the (padded)
    array dims when known (``M/D/N``, ``T``, flash ``D/Dv/Tq/Tk``) —
    unknown dims disable the divide/whole-dim checks but never the
    multiple checks.
    """
    d = dict(dims or {})
    K = int(n_digits)
    g = d.get
    if kind == "rns_matmul":
        bm, bn, bk = blocks["bm"], blocks["bn"], blocks["bk"]
        # the moduli sit whole in SMEM: no VMEM tile
        tiles = [
            ("a_res", (1, bm, bk), res_bytes, (K, g("M"), g("D"))),
            ("b_res", (1, bk, bn), res_bytes, (K, g("D"), g("N"))),
            ("out", (1, bm, bn), 4, (K, g("M"), g("N"))),
        ]
        scratch = [((bm, bn), 4)]
    elif kind == "rns_fused_encode_matmul":
        bm, bn, bk = blocks["bm"], blocks["bn"], blocks["bk"]
        tiles = [
            ("x", (bm, bk), 4, (g("M"), g("D"))),
            ("scale", (bm, 1), 4, (g("M"), 1)),
            ("b_res", (1, bk, bn), res_bytes, (K, g("D"), g("N"))),
            ("out", (1, bm, bn), 4, (K, g("M"), g("N"))),
        ]
        scratch = [((bm, bn), 4)]
    elif kind == "rns_fused_matmul_normalize":
        bm, bn, bk = blocks["bm"], blocks["bn"], blocks["bk"]
        tiles = [
            ("a_res", (K, bm, bk), res_bytes, (K, g("M"), g("D"))),
            ("b_res", (K, bk, bn), res_bytes, (K, g("D"), g("N"))),
            ("out", (bm, bn), 4, (g("M"), g("N"))),
        ]
        scratch = [((K, bm, bn), 4)]
    elif kind == "rns_fused_dot":
        bm, bn, bk = blocks["bm"], blocks["bn"], blocks["bk"]
        tiles = [
            ("x", (bm, bk), 4, (g("M"), g("D"))),
            ("scale", (bm, 1), 4, (g("M"), 1)),
            ("b_res", (K, bk, bn), res_bytes, (K, g("D"), g("N"))),
            ("out", (bm, bn), 4, (g("M"), g("N"))),
        ]
        scratch = [((K, bm, bn), 4)]
    elif kind == "rns_convert":
        bt = blocks["bt"]
        # scale modeled per-element — the conservative case; scalar
        # callers stream a (1, 1) broadcast block instead.
        tiles = [
            ("x", (bt,), 4, (g("T"),)),
            ("scale", (bt,), 4, (g("T"),)),
            ("out", (K, bt), res_bytes, (K, g("T"))),
        ]
        scratch = []
    elif kind == "rns_normalize":
        bt = blocks["bt"]
        tiles = [
            ("res", (K, bt), 4, (K, g("T"))),
            ("out", (bt,), 4, (g("T"),)),
        ]
        scratch = []
    elif kind == "flash_attention":
        bq, bkf = blocks["bq"], blocks["bk"]
        D = g("D", 128)
        Dv = g("Dv", 128)
        tiles = [
            ("q", (1, bq, D), 4, (None, g("Tq"), D)),
            ("k", (1, bkf, D), 4, (None, g("Tk"), D)),
            ("v", (1, bkf, Dv), 4, (None, g("Tk"), Dv)),
            ("out", (1, bq, Dv), 4, (None, g("Tq"), Dv)),
        ]
        scratch = [((bq, 1), 4), ((bq, 1), 4), ((bq, Dv), 4)]
    else:
        raise KeyError(f"unknown kernel kind {kind!r}")
    return tiles, scratch


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def vmem_bytes(kind, blocks, *, n_digits=1, res_bytes=4, dims=None) -> int:
    """Closed-form VMEM working set: ``2 * streamed-block bytes +
    scratch bytes`` (the double-buffering model; see module docstring)."""
    tiles, scratch = _block_layout(kind, blocks, n_digits, res_bytes, dims)
    streamed = sum(_prod(b) * eb for _, b, eb, _ in tiles)
    return 2 * streamed + sum(_prod(s) * eb for s, eb in scratch)


def validate_blocks(kind, blocks, *, n_digits=1, res_bytes=4,
                    dims=None) -> list[str]:
    """All legality violations of a block dict for one kernel kind.

    Empty list == the config is statically proven Mosaic-legal and
    within the VMEM budget for the given profile/dims.  Tolerates junk
    input (missing keys, non-int sizes) by *naming* it rather than
    raising — this is the autotune cache gate.
    """
    if kind not in _REQUIRED:
        return [f"unknown kernel kind {kind!r}"]
    if not isinstance(blocks, dict):
        return [f"{kind}: blocks is {type(blocks).__name__}, not a dict"]
    bad = []
    for name in _REQUIRED[kind]:
        v = blocks.get(name)
        if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
            bad.append(f"{kind}: block {name!r} is {v!r} "
                       "(need a positive int)")
    if bad:
        return bad
    tiles, _ = _block_layout(kind, blocks, n_digits, res_bytes, dims)
    out = []
    for label, block, eb, full in tiles:
        out.extend(_tile_violations(f"{kind}.{label}", block, eb, full))
    vm = vmem_bytes(kind, blocks, n_digits=n_digits, res_bytes=res_bytes,
                    dims=dims)
    if vm > BUDGET_BYTES:
        out.append(f"{kind}: VMEM working set {vm} bytes exceeds the "
                   f"{BUDGET_BYTES}-byte per-core budget")
    return out


@functools.lru_cache(maxsize=4096)
def _check_cached(kind, block_items, dim_items, n_digits, res_bytes):
    blocks = dict(block_items)
    dims = dict(dim_items)
    violations = validate_blocks(kind, blocks, n_digits=n_digits,
                                 res_bytes=res_bytes, dims=dims)
    if violations:
        try:
            vm = str(vmem_bytes(kind, blocks, n_digits=n_digits,
                                res_bytes=res_bytes, dims=dims))
        except (KeyError, TypeError):
            vm = "n/a"
        raise BlockConfigError(
            f"{kind}: illegal block config {blocks} (VMEM working set "
            f"{vm} bytes vs budget {BUDGET_BYTES}): "
            + "; ".join(violations))
    return True


def check_wrapper_blocks(kind, blocks, *, dims, n_digits=1,
                         res_bytes=4) -> None:
    """Wrapper-side gate: raise ``ValueError`` naming the kernel, the
    blocks, and the computed VMEM bytes if the (resolved, padded) config
    is illegal — instead of failing deep inside Mosaic lowering.  Legal
    configs are memoized so the trace-time cost is one dict lookup."""
    _check_cached(kind, tuple(sorted(blocks.items())),
                  tuple(sorted((dims or {}).items())),
                  int(n_digits), int(res_bytes))


# ---------------------------------------------------------------------------
# capture layer: eval_shape with pallas_call shimmed out
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelLaunch:
    """One recorded ``pallas_call``: everything the legality checks need."""

    kind: str
    kernel_name: str
    profile: str | None
    grid: tuple
    in_specs: tuple    # ((block_shape, index_map), ...); (None, None)
                       # for an operand placed whole outside VMEM
    out_specs: tuple
    operands: tuple    # ((shape, dtype_str, itemsize), ...)
    outs: tuple
    scratch: tuple     # ((shape, itemsize), ...)


def _clear_tile_caches() -> None:
    """Drop every jitted ``*_tiles`` entry point's compile cache.

    Called before a capture (so the python bodies re-run through the
    shim instead of replaying a cached jaxpr) and after (so the
    zeros-returning shim trace can never serve a real call)."""
    from repro.kernels.flash_attention.kernel import flash_attention_bhtd
    from repro.kernels.rns_convert.kernel import rns_convert_tiles
    from repro.kernels.rns_fused.kernel import (
        rns_fused_dot_tiles,
        rns_fused_encode_matmul_tiles,
        rns_fused_matmul_normalize_tiles,
    )
    from repro.kernels.rns_matmul.kernel import rns_matmul_tiles
    from repro.kernels.rns_normalize.kernel import rns_normalize_tiles

    for fn in (rns_matmul_tiles, rns_convert_tiles, rns_normalize_tiles,
               rns_fused_encode_matmul_tiles, rns_fused_matmul_normalize_tiles,
               rns_fused_dot_tiles, flash_attention_bhtd):
        fn.clear_cache()


def _kernel_identity(fn):
    """Unwrap a (possibly partial'd) kernel fn to (kind, name, profile)."""
    kw = {}
    while isinstance(fn, functools.partial):
        for k, v in (fn.keywords or {}).items():
            kw.setdefault(k, v)
        fn = fn.func
    mod = getattr(fn, "__module__", "") or ""
    name = getattr(fn, "__name__", "<kernel>")
    seg = mod.split(".kernels.", 1)[1].split(".", 1)[0] \
        if ".kernels." in mod else mod
    kind = _KIND_BY_FN.get((seg, name), f"{seg}.{name}")
    prof = kw.get("profile")
    return kind, name, (prof if isinstance(prof, str)
                        else getattr(prof, "name", None))


def _spec_entry(spec):
    """``(block_shape, index_map)`` of a BlockSpec; ``(None, None)`` for a
    memory-space spec with no block shape, which places the whole operand
    (e.g. the moduli in SMEM) instead of streaming tiles of it.  Such a
    spec is never in VMEM: the lint's ``whole-array-vmem`` rule refuses
    whole-array VMEM specs."""
    if spec.block_shape is None:
        return None, None
    return tuple(spec.block_shape), spec.index_map


def capture_launches(fn, *args, **kwargs) -> list[KernelLaunch]:
    """Abstract-interpret ``fn`` (zero FLOPs) recording every pallas_call.

    ``jax.eval_shape`` runs the wrapper python under a shim installed on
    ``jax.experimental.pallas.pallas_call`` that records the launch and
    returns zeros of ``out_shape`` — so padding/reshape logic runs as
    written and the recorded grid/BlockSpecs are the real ones.  The
    jitted ``*_tiles`` compile caches are cleared on both sides of the
    capture (see :func:`_clear_tile_caches`)."""
    import jax
    import jax.experimental.pallas as pl_mod
    import jax.numpy as jnp

    captured: list[KernelLaunch] = []

    def fake_pallas_call(kernel, *fargs, out_shape=None, grid=None,
                         in_specs=None, out_specs=None, scratch_shapes=None,
                         **_kw):
        if fargs and out_shape is None:
            out_shape = fargs[0]
        kind, kname, prof = _kernel_identity(kernel)
        grid_t = (grid,) if isinstance(grid, int) else tuple(grid or ())
        ins = tuple(_spec_entry(s) for s in (in_specs or []))
        out_spec_list = (list(out_specs) if isinstance(out_specs, (list, tuple))
                         else [out_specs])
        outs_t = tuple(_spec_entry(s) for s in out_spec_list if s is not None)
        scratch = tuple((tuple(s.shape), jnp.dtype(s.dtype).itemsize)
                        for s in (scratch_shapes or []))
        out_leaves = jax.tree_util.tree_leaves(out_shape)

        def runner(*operands):
            captured.append(KernelLaunch(
                kind=kind, kernel_name=kname, profile=prof, grid=grid_t,
                in_specs=ins, out_specs=outs_t,
                operands=tuple(
                    (tuple(o.shape), str(o.dtype),
                     jnp.dtype(o.dtype).itemsize) for o in operands),
                outs=tuple(
                    (tuple(s.shape), str(s.dtype),
                     jnp.dtype(s.dtype).itemsize) for s in out_leaves),
                scratch=scratch))
            return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                out_shape)

        return runner

    with _CAPTURE_LOCK:
        real = pl_mod.pallas_call
        _clear_tile_caches()
        pl_mod.pallas_call = fake_pallas_call
        try:
            jax.eval_shape(fn, *args, **kwargs)
        finally:
            pl_mod.pallas_call = real
            _clear_tile_caches()
    return captured


def _grid_points(grid):
    """Grid points to probe: exhaustive when small, corners otherwise."""
    if _prod(grid) <= _MAX_ENUM:
        return list(itertools.product(*(range(g) for g in grid))), True
    axes = [sorted({0, g // 2, g - 1}) for g in grid]
    return list(itertools.product(*axes)), False


def _check_spec(kind, label, block, index_map, full, elem_bytes, grid,
                points, exhaustive, want_cover):
    """Tiling + coverage checks for one (BlockSpec, operand) pair."""
    out = list(_tile_violations(f"{kind}.{label}", block, elem_bytes, full))
    if len(block) != len(full):
        out.append(f"{kind}.{label}: block rank {len(block)} != operand "
                   f"rank {len(full)}")
        return out
    if out:
        return out
    seen = set()
    for pt in points:
        try:
            idx = index_map(*pt)
        except TypeError:
            out.append(f"{kind}.{label}: index_map arity != grid rank "
                       f"{len(grid)}")
            return out
        idx = tuple(int(i) for i in idx)
        if len(idx) != len(block):
            out.append(f"{kind}.{label}: index_map returns {len(idx)} "
                       f"indices for a rank-{len(block)} block")
            return out
        for d, (i, b, f) in enumerate(zip(idx, block, full)):
            if i < 0 or (i + 1) * b > f:
                out.append(
                    f"{kind}.{label}: grid point {pt} reads block "
                    f"{idx} — dim {d} spans [{i * b}, {(i + 1) * b}) "
                    f"outside array dim {f}")
                return out
        seen.add(idx)
    if want_cover and exhaustive:
        tiles_needed = _prod(f // b for f, b in zip(full, block))
        if len(seen) != tiles_needed:
            out.append(
                f"{kind}.{label}: grid writes {len(seen)} distinct "
                f"blocks but the output has {tiles_needed} tiles — "
                "output not fully covered")
    return out


def check_launch(launch: KernelLaunch) -> list[str]:
    """All legality violations of one captured launch (empty == proved).

    Checks: Mosaic tiling of every in/out BlockSpec against its operand
    aval, grid x index_map block reads in bounds, every output tile
    written exactly once per pass, the double-buffered VMEM working set
    against :data:`BUDGET_BYTES`, and — for the fused matmul+normalize
    kernels — that the digit-axis scratch ``[K, bm, bn]`` covers every
    digit (K resident, never grid-tiled)."""
    kind = launch.kind
    out = []
    if len(launch.in_specs) != len(launch.operands):
        out.append(f"{kind}: {len(launch.in_specs)} in_specs for "
                   f"{len(launch.operands)} operands")
        return out
    if len(launch.out_specs) != len(launch.outs):
        out.append(f"{kind}: {len(launch.out_specs)} out_specs for "
                   f"{len(launch.outs)} outputs")
        return out
    points, exhaustive = _grid_points(launch.grid)
    for i, ((block, imap), (shape, _dt, eb)) in enumerate(
            zip(launch.in_specs, launch.operands)):
        if block is None:   # placed whole: no tiles, nothing to cover
            continue
        out.extend(_check_spec(kind, f"in{i}", block, imap, shape, eb,
                               launch.grid, points, exhaustive, False))
    for i, ((block, imap), (shape, _dt, eb)) in enumerate(
            zip(launch.out_specs, launch.outs)):
        out.extend(_check_spec(kind, f"out{i}", block, imap, shape, eb,
                               launch.grid, points, exhaustive, True))
    vm = launch_vmem_bytes(launch)
    if vm > BUDGET_BYTES:
        out.append(f"{kind}: VMEM working set {vm} bytes exceeds the "
                   f"{BUDGET_BYTES}-byte per-core budget")
    b_idx = _RESIDENT_B_OPERAND.get(kind)
    if b_idx is not None and b_idx < len(launch.operands):
        K = launch.operands[b_idx][0][0]  # b_res [K, D, N] leading dim
        if not launch.scratch or launch.scratch[0][0][:1] != (K,):
            got = launch.scratch[0][0] if launch.scratch else None
            out.append(f"{kind}: digit-axis scratch is {got} — must be "
                       f"[K={K}, bm, bn] resident")
        if b_idx < len(launch.in_specs) and \
                launch.in_specs[b_idx][0][0] != K:
            out.append(
                f"{kind}: weight-residue block leading dim "
                f"{launch.in_specs[b_idx][0][0]} != K={K} — the digit "
                "axis must stay resident, not grid-tiled")
    return out


def launch_vmem_bytes(launch: KernelLaunch) -> int:
    """Double-buffered working set of a captured launch, in bytes
    (operands placed whole outside VMEM take none)."""
    streamed = sum(
        _prod(block) * eb
        for (block, _), (_, _, eb) in
        list(zip(launch.in_specs, launch.operands))
        + list(zip(launch.out_specs, launch.outs))
        if block is not None)
    return 2 * streamed + sum(_prod(s) * eb for s, eb in launch.scratch)


# ---------------------------------------------------------------------------
# report layer: sweep kinds x shapes x configs, audit engines
# ---------------------------------------------------------------------------


def _profile_meta(kind, profile):
    """(n_digits, residue element bytes) for a (kind, profile) pair."""
    if kind == "flash_attention":
        return 1, 4
    from repro.core.moduli import get_profile

    p = get_profile(profile) if isinstance(profile, str) else profile
    return p.n_digits, (1 if p.int8_safe else 4)


def _capture_kind(kind, profile, shape, blocks) -> list[KernelLaunch]:
    """Capture the real wrapper's launches for one shape + block config."""
    import jax
    import jax.numpy as jnp

    def spec(s, dt):
        return jax.ShapeDtypeStruct(tuple(s), dt)

    n_digits, res_bytes = _profile_meta(kind, profile)
    rdt = jnp.int8 if res_bytes == 1 else jnp.int32
    if kind == "rns_matmul":
        from repro.kernels.rns_matmul.ops import rns_matmul

        M, D, N = shape
        return capture_launches(
            lambda a, b: rns_matmul(profile, a, b, **blocks),
            spec((n_digits, M, D), rdt), spec((n_digits, D, N), rdt))
    if kind == "rns_fused_encode_matmul":
        from repro.kernels.rns_fused.ops import rns_fused_encode_matmul

        M, D, N = shape
        return capture_launches(
            lambda x, s, b: rns_fused_encode_matmul(profile, x, s, b,
                                                    **blocks),
            spec((M, D), jnp.float32), spec((), jnp.float32),
            spec((n_digits, D, N), rdt))
    if kind == "rns_fused_matmul_normalize":
        from repro.kernels.rns_fused.ops import rns_fused_matmul_normalize

        M, D, N = shape
        return capture_launches(
            lambda a, b: rns_fused_matmul_normalize(profile, a, b, **blocks),
            spec((n_digits, M, D), rdt), spec((n_digits, D, N), rdt))
    if kind == "rns_fused_dot":
        from repro.kernels.rns_fused.ops import rns_fused_dot

        M, D, N = shape
        return capture_launches(
            lambda x, s, b: rns_fused_dot(profile, x, s, b, **blocks),
            spec((M, D), jnp.float32), spec((), jnp.float32),
            spec((n_digits, D, N), rdt))
    if kind == "rns_convert":
        from repro.kernels.rns_convert.ops import rns_convert

        (T,) = shape
        return capture_launches(
            lambda x, s: rns_convert(profile, x, s, out_dtype=rdt, **blocks),
            spec((T,), jnp.float32), spec((), jnp.float32))
    if kind == "rns_normalize":
        from repro.kernels.rns_normalize.ops import rns_normalize

        (T,) = shape
        return capture_launches(
            lambda r: rns_normalize(profile, r, **blocks),
            spec((n_digits, T), jnp.int32))
    if kind == "flash_attention":
        from repro.kernels.flash_attention.ops import flash_attention

        B, Tq, Tk, H, Hk, D, Dv = shape
        return capture_launches(
            lambda q, k, v: flash_attention(q, k, v, causal=True, **blocks),
            spec((B, Tq, H, D), jnp.float32),
            spec((B, Tk, Hk, D), jnp.float32),
            spec((B, Tk, Hk, Dv), jnp.float32))
    raise KeyError(f"unknown kernel kind {kind!r}")


def audit_config(kind, profile, shape, blocks, source="defaults") -> dict:
    """Audit ONE (kind, profile, shape, blocks) config, both layers.

    The closed-form model and the captured launches must *agree*: a
    config is ok only if the block dict validates, the wrapper builds
    (its own guard may refuse first — that failure is recorded, not
    raised), and every captured launch passes :func:`check_launch`.  The
    capture also cross-checks that the closed-form VMEM model is
    conservative (captured working set <= modeled)."""
    n_digits, res_bytes = _profile_meta(kind, profile)
    violations = list(validate_blocks(kind, blocks, n_digits=n_digits,
                                      res_bytes=res_bytes))
    entry = {
        "kind": kind, "profile": str(profile), "shape": list(shape),
        "source": source, "blocks": dict(blocks),
        "grid": None, "vmem_bytes": None, "n_launches": 0,
    }
    launches: list[KernelLaunch] = []
    try:
        launches = _capture_kind(kind, profile, shape, blocks)
    except ValueError as e:  # the wrapper guard refused the config
        violations.append(f"{kind}: wrapper refused to build: {e}")
    model_vm = None
    if not any("positive int" in v for v in violations):
        model_vm = vmem_bytes(kind, blocks, n_digits=n_digits,
                              res_bytes=res_bytes)
    for ln in launches:
        violations.extend(check_launch(ln))
        vm = launch_vmem_bytes(ln)
        entry["grid"] = list(ln.grid)
        entry["vmem_bytes"] = max(entry["vmem_bytes"] or 0, vm)
        if model_vm is not None and vm > model_vm:
            violations.append(
                f"{kind}: captured working set {vm} bytes exceeds the "
                f"closed-form model {model_vm} — the VMEM model is not "
                "conservative")
    if entry["vmem_bytes"] is None:
        entry["vmem_bytes"] = model_vm
    entry["n_launches"] = len(launches)
    # dedupe, preserving order (closed-form + capture often agree)
    entry["violations"] = list(dict.fromkeys(violations))
    entry["ok"] = not entry["violations"]
    return entry


@dataclasses.dataclass
class KernelAuditReport:
    """Result of a kernel-legality sweep (``audit_all`` / engine audit)."""

    ok: bool
    entries: list
    budget_bytes: int = BUDGET_BYTES

    @property
    def failed(self) -> list:
        return [e for e in self.entries if not e["ok"]]

    def summary(self) -> str:
        if self.ok:
            kinds = sorted({e["kind"] for e in self.entries})
            return (f"kernel audit: PROVED ({len(self.entries)} configs "
                    f"across {len(kinds)} kernels, all Mosaic-legal, "
                    f"VMEM <= {self.budget_bytes} bytes)")
        bad = self.failed
        head = bad[0]
        return (f"kernel audit: FAILED ({len(bad)}/{len(self.entries)} "
                f"configs illegal; first: {head['kind']} "
                f"{head['blocks']} [{head['source']}] — "
                f"{head['violations'][0]})")

    def table(self) -> str:
        rows = ["kind | profile | shape | source | blocks | vmem_bytes | ok"]
        for e in self.entries:
            blocks = ",".join(f"{k}={v}" for k, v in e["blocks"].items())
            shape = "x".join(str(s) for s in e["shape"])
            rows.append(
                f"{e['kind']} | {e['profile']} | {shape} | {e['source']} "
                f"| {blocks} | {e['vmem_bytes']} | "
                f"{'ok' if e['ok'] else 'FAIL: ' + e['violations'][0]}")
        return "\n".join(rows)

    def to_dict(self) -> dict:
        return {"ok": self.ok, "budget_bytes": self.budget_bytes,
                "summary": self.summary(), "entries": self.entries}

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)


def audit_all(profiles=("rns6", "rns9"), include_candidates=True,
              include_cache=True) -> KernelAuditReport:
    """Sweep every kernel family x shape bucket x block config.

    Configs audited per (kind, profile, shape): the autotune DEFAULTS,
    every autotune CANDIDATE (when ``include_candidates``), and any
    persisted autotune cache row for the kind (when ``include_cache``) —
    so a stale tuned row is proved or named just like the shipped
    search space.  flash_attention has no RNS profile; it is audited
    once under the pseudo-profile ``float32``."""
    from repro.kernels import autotune

    entries = []
    for kind, shapes in _AUDIT_SHAPES.items():
        profs = ("float32",) if kind == "flash_attention" else tuple(profiles)
        configs: list[tuple[str, dict]] = [
            ("defaults", dict(autotune.DEFAULTS[kind]))]
        if include_candidates:
            configs += [(f"candidate[{i}]", dict(c)) for i, c in
                        enumerate(autotune.CANDIDATES.get(kind, ()))]
        if include_cache:
            seen = {tuple(sorted(c.items())) for _, c in configs}
            for key, row in sorted(autotune._load().items()):
                if key.split("|", 1)[0] != kind:
                    continue
                blocks = dict(autotune.DEFAULTS[kind], **row["blocks"])
                if tuple(sorted(blocks.items())) not in seen:
                    seen.add(tuple(sorted(blocks.items())))
                    configs.append((f"cache[{key}]", blocks))
        for prof in profs:
            for shape in shapes:
                for source, blocks in configs:
                    entries.append(
                        audit_config(kind, prof, shape, blocks, source))
    return KernelAuditReport(ok=all(e["ok"] for e in entries),
                             entries=entries)


def audit_engine_kernels(engine) -> KernelAuditReport:
    """Audit the pallas launches of a built engine's own jitted phases.

    Captures each ``engine._trace_specs()`` closure — the exact programs
    the engine serves — and checks every recorded launch.  An engine
    whose backend never lowers to Pallas (reference) records zero
    launches and is trivially proved.  This is the kernel half of the
    ``ServeConfig(audit=True)`` build gate."""
    entries = []
    for phase, (fn, args) in engine._trace_specs().items():
        try:
            launches = capture_launches(fn, *args)
        except ValueError as e:
            entries.append({
                "kind": f"engine.{phase}", "profile": None,
                "shape": [], "source": "engine", "blocks": {},
                "grid": None, "vmem_bytes": None, "n_launches": 0,
                "violations": [f"engine phase {phase!r} refused to "
                               f"build: {e}"],
                "ok": False})
            continue
        violations = []
        vmem = None
        for ln in launches:
            for v in check_launch(ln):
                violations.append(f"[{ln.kind}] {v}")
            vmem = max(vmem or 0, launch_vmem_bytes(ln))
        entries.append({
            "kind": f"engine.{phase}", "profile": None, "shape": [],
            "source": "engine",
            "blocks": {}, "grid": None, "vmem_bytes": vmem,
            "n_launches": len(launches),
            "violations": list(dict.fromkeys(violations)),
            "ok": not violations})
    return KernelAuditReport(ok=all(e["ok"] for e in entries),
                             entries=entries)
