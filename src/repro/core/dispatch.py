"""Backend dispatch for the three RNS execution primitives.

Every residue-domain computation in the repo reduces to three primitives
(the paper's Fig. 5 blocks):

  * ``convert``   — forward conversion: fixed-point quantize + per-digit
                    modular reduction (cheap, O(K) PAC work per element).
  * ``matmul``    — digit-sliced modular matmul (the carry-free PAC array).
  * ``normalize`` — MRC normalization to signed values (the ONE slow
                    O(K^2) op; everything above defers to it).

This module is the single place that decides *which implementation* runs:
the pure-jnp reference, the compiled Pallas TPU kernels, or the Pallas
interpreter (CPU-testable).  It replaces the ``use_pallas`` / per-wrapper
``interpret`` flag plumbing that used to be scattered across
``core/rns_matmul.py`` and the four ``kernels/*/ops.py`` wrappers.

It also owns the op counters behind the deferred-normalization claim:
``count_ops()`` tallies primitive invocations at trace time, so tests and
benchmarks can assert "one normalize per chain" structurally instead of
timing it.

Fused composites (``pallas_fused`` / ``pallas_fused_interpret``): the
paper's Fig. 5 datapath is one wired pipeline, and
``fused_encode_matmul`` / ``fused_matmul_normalize`` / ``fused_dot``
run it as single Pallas kernels (kernels/rns_fused) — bit-identical to
the three-stage chain, without the [K, ..., D] residue-plane and
[K, ..., N] accumulator round-trips through HBM.  On non-fused backends
(or under a digit-sharding context, or for non-row-foldable scales) the
composites decompose into the primitives, so call sites stay uniform;
visible downgrades tally ``fallbacks``.  See docs/kernels.md.

Mesh-aware path (residue-channel sharding): when a
``distributed.sharding.use_digit_sharding`` context is installed and the
profile's digit count divides the digit mesh axis, the three primitives
route through per-device ``shard_map`` bodies instead.  Each device owns
``K / n`` moduli; ``convert`` and ``matmul`` then compile to strictly
local work — the HLO of a residue segment contains ZERO cross-device
collectives (asserted in tests/test_distributed_rns.py) because RNS
digits never exchange carries.  Digits meet exactly once, inside
``normalize``: its body all-gathers the digit axis and runs the MRC
replicated.  The sharded bodies use the reference math (fusing the Pallas
kernels into per-device digit slices needs per-slice constant tables and
is future work), so an explicit ``backend=`` still wins only off-mesh.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "BACKENDS",
    "resolve_backend",
    "set_default_backend",
    "default_interpret",
    "is_fused",
    "OpCounts",
    "count_ops",
    "trace_op_counts",
    "record_ops",
    "recording",
    "record_op",
    "annotate_digits",
    "convert",
    "matmul",
    "normalize",
    "fused_encode_matmul",
    "fused_matmul_normalize",
    "fused_dot",
]

#: reference              — pure jnp (works everywhere; exactness oracle)
#: pallas                 — compiled Pallas TPU kernels (interpret auto on CPU)
#: pallas_interpret       — Pallas kernels forced through the interpreter
#: pallas_fused           — pallas + the fused composite kernels
#:                          (kernels/rns_fused) at the fused_* call sites
#: pallas_fused_interpret — same, forced through the interpreter
BACKENDS = ("reference", "pallas", "pallas_interpret", "pallas_fused",
            "pallas_fused_interpret")

#: the per-primitive (convert/matmul/normalize) behaviour of a fused
#: backend is its unfused pallas equivalent; only the fused_* composite
#: entry points below change what actually runs.
_FUSED_TO_UNFUSED = {"pallas_fused": "pallas",
                     "pallas_fused_interpret": "pallas_interpret"}

_state = threading.local()      # per-thread op-counter stacks
_default_backend = "auto"       # process-wide (module global)


def _default() -> str:
    return _default_backend


def set_default_backend(name: str | None):
    """Process-wide default for ``backend=None``/"auto" call sites."""
    global _default_backend
    if name is not None and name != "auto" and name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; have {BACKENDS}")
    _default_backend = name or "auto"


def resolve_backend(name: str | None = None) -> str:
    """Map None/"auto" to the hardware-appropriate backend."""
    name = name or _default()
    if name == "auto":
        name = _default()
    if name == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "reference"
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; have {BACKENDS}")
    return name


def default_interpret() -> bool:
    """Whether a Pallas kernel should run in interpret mode by default.

    The single source of truth for the decision the four kernel wrappers
    used to each make on their own.
    """
    return jax.default_backend() == "cpu"


def is_fused(name: str | None = None) -> bool:
    """Whether the (resolved) backend routes composites through the
    fused kernels."""
    return resolve_backend(name) in _FUSED_TO_UNFUSED


def fusion_active(profile, backend: str | None = None) -> bool:
    """Would the composites actually launch fused kernels here?

    False under a digit-sharding context that splits this profile: the
    shard_map bodies own the layout there, so callers should keep their
    unfused structure (e.g. ``rns_multi_dot``'s shared conversion)
    instead of asking a composite that would only decompose."""
    if not is_fused(backend):
        return False
    ds, _ = _digit_ctx(profile)
    return ds is None


def _interpret_for(backend: str) -> bool | None:
    # "pallas" lets the wrapper consult default_interpret(); the forced
    # variants pin the interpreter regardless of platform.
    if backend in ("pallas_interpret", "pallas_fused_interpret"):
        return True
    return None


# ------------------------------------------------------------ counters ----
@dataclasses.dataclass(eq=False)  # identity semantics: counters nest
class OpCounts:
    """Primitive tallies (trace-time; one per call site reached).

    A fused composite tallies its constituent logical ops (a fused
    encode+matmul is still one convert and one matmul — the structural
    deferred-normalization claims stay backend-independent) PLUS one
    ``fused`` entry per composite kernel launch.  ``fallbacks`` counts
    requested-backend downgrades (e.g. a normalize whose inv_scale
    escapes float32 range), which used to masquerade as pallas ops.

    ``weight_converts`` is the subset of ``converts`` spent re-encoding
    *static weights* (call sites pass ``convert(..., weight=True)``).
    On the resident-weight path it is zero — weights are encoded once at
    build time — so "resident equals re-encode minus weight converts" is
    a structural assertion: compare ``activation_converts`` across paths.

    ``fallback_sites`` refines the ``fallbacks`` counter into a per-site
    tally keyed by ``(site, reason)`` — ``site`` is the nearest caller
    frame outside this module (``"core/tensor.py:rt_decode"``-style) —
    so the auditor and the backend matrix can assert *which* downgrades
    happened, not just how many.  The int counter is preserved and always
    equals ``sum(fallback_sites.values())``.
    """

    converts: int = 0
    matmuls: int = 0
    normalizes: int = 0
    fused: int = 0
    fallbacks: int = 0
    weight_converts: int = 0
    fallback_sites: dict = dataclasses.field(default_factory=dict)

    @property
    def normalizes_per_matmul(self) -> float:
        return self.normalizes / max(self.matmuls, 1)

    @property
    def activation_converts(self) -> int:
        return self.converts - self.weight_converts

    def add(self, other: "OpCounts", times: int = 1) -> "OpCounts":
        """New OpCounts = self + times * other (per-site tallies merged)."""
        out = OpCounts(**{f: getattr(self, f) + times * getattr(other, f)
                          for f in ("converts", "matmuls", "normalizes",
                                    "fused", "fallbacks", "weight_converts")})
        out.fallback_sites = dict(self.fallback_sites)
        for k, n in other.fallback_sites.items():
            out.fallback_sites[k] = out.fallback_sites.get(k, 0) + times * n
        return out


def _counters() -> list[OpCounts]:
    if not hasattr(_state, "counters"):
        _state.counters = []
    return _state.counters


def _tally(field: str):
    for c in _counters():
        setattr(c, field, getattr(c, field) + 1)


@contextlib.contextmanager
def count_ops():
    """Count primitive invocations (including inside jit *tracing*)."""
    c = OpCounts()
    _counters().append(c)
    try:
        yield c
    finally:
        _counters().remove(c)


def trace_op_counts(fn, *args, **kwargs) -> OpCounts:
    """Counts for one abstract evaluation of ``fn`` (no FLOPs spent)."""
    with count_ops() as c:
        jax.eval_shape(fn, *args, **kwargs)
    return c


# ----------------------------------------------------------- recorders ----
# The abstract-interpretation shim behind repro.analysis: while a recorder
# is installed (record_ops), every primitive/composite call reports the
# operand and output *objects* (tracers under jax.eval_shape) plus static
# metadata (profile, quantize bits, contraction dim, resolved backend,
# what it tallied).  Recorders link operands to producers by object
# identity and keep the objects alive so ids stay unique; ledger-level
# call sites (core/tensor.py) add tensor annotations — ground-truth
# mag_bits for digit arrays whose producer the shim cannot see (resident
# weights, dtype casts).  Recording costs nothing when no recorder is
# installed and never changes what executes.

def _recorders() -> list:
    if not hasattr(_state, "recorders"):
        _state.recorders = []
    return _state.recorders


def recording() -> bool:
    """Whether an analysis recorder is installed on this thread."""
    return bool(_recorders())


@contextlib.contextmanager
def record_ops(recorder):
    """Install ``recorder`` (``.record(...)``/``.annotate(...)`` duck
    type; see ``repro.analysis.graph.GraphRecorder``) for the dynamic
    extent, nested like ``count_ops``."""
    _recorders().append(recorder)
    try:
        yield recorder
    finally:
        _recorders().remove(recorder)


_THIS_FILE = __file__


def _call_site() -> str:
    """Nearest repro frame outside this module, plus the nearest frame
    outside ``core/`` when that differs — ``"models/layers.py:mlp ->
    core/tensor.py:rt_decode"``-style, stable across traces."""
    f = sys._getframe(1)
    inner = outer = None
    while f is not None:
        fname = f.f_code.co_filename.replace("\\", "/")
        if "/repro/" in fname and fname != _THIS_FILE:
            rel = fname.rsplit("/repro/", 1)[1]
            label = f"{rel}:{f.f_code.co_name}"
            if inner is None:
                inner = label
            if not rel.startswith("core/"):
                outer = label
                break
        f = f.f_back
    if inner is None:
        return "<external>"
    if outer is not None and outer != inner:
        return f"{outer} -> {inner}"
    return inner


def record_op(kind: str, out, ins: tuple = (), **meta):
    """Report one recorded op to the installed recorders (no-op without
    one).  Ledger-level call sites use this for ops that do not route
    through the primitives below (``rns_mul``/``rns_add``, forced
    renormalizes)."""
    rs = _recorders()
    if not rs:
        return
    site = meta.pop("site", None) or _call_site()
    for r in rs:
        r.record(kind, out, ins, site=site, **meta)


def annotate_digits(arr, **meta):
    """Attach ground-truth ledger facts (``mag_bits``, ``profile``,
    ``frac_exp``, ``role``, optional ``base`` array whose ledger state
    ``arr`` aliases) to a digit array object for the installed
    recorders."""
    rs = _recorders()
    if not rs:
        return
    for r in rs:
        r.annotate(arr, **meta)


def _tally_fallback(reason: str):
    """A visible backend downgrade: bump the counters (total + per-site)
    and report a ``fallback`` event to the recorders."""
    cs, rs = _counters(), _recorders()
    if not cs and not rs:
        return
    site = _call_site()
    for c in cs:
        c.fallbacks += 1
        key = (site, reason)
        c.fallback_sites[key] = c.fallback_sites.get(key, 0) + 1
    for r in rs:
        r.record("fallback", None, (), site=site, reason=reason,
                 tallies={"fallbacks": 1})


def _prof_name(profile) -> str:
    return profile if isinstance(profile, str) else profile.name


def _emit(kind: str, out, ins: tuple, **meta):
    rs = _recorders()
    if not rs:
        return
    site = _call_site()
    for r in rs:
        r.record(kind, out, ins, site=site, **meta)


# ------------------------------------------------- digit-sharded bodies ----
def _digit_ctx(profile):
    """The installed DigitSharding if it actually splits this profile."""
    from repro.core.moduli import get_profile
    from repro.distributed.sharding import digit_sharding

    ds = digit_sharding()
    if ds is None:
        return None, None
    p = get_profile(profile) if isinstance(profile, str) else profile
    return (ds, p) if ds.shards(p.n_digits) else (None, p)


def _moduli_arr(p) -> jax.Array:
    return jnp.asarray(np.asarray(p.moduli, np.int32))


def _jit_shard_map(f, ds, in_specs, out_specs):
    # manual over the digit axis only; every other mesh axis stays with
    # GSPMD.  Partly-manual shard_map exists only under jit: the wrapper
    # keeps eager call sites working and inlines under outer jits
    mapped = jax.shard_map(f, mesh=ds.mesh, in_specs=in_specs,
                           out_specs=out_specs, axis_names={ds.axis},
                           check_vma=False)
    return jax.jit(mapped)


# The builders below are lru_cached on their static parameters (the
# frozen DigitSharding — Mesh is hashable — and the frozen RnsProfile,
# so unregistered profile objects work exactly as on the unsharded
# paths): a fresh closure per call would defeat jit's function-identity
# cache and recompile every eager invocation.

@functools.lru_cache(maxsize=512)
def _sharded_convert_fn(ds, p, bits, xndim, sndim):
    """Forward conversion, one digit group per device, zero collectives.

    The local moduli arrive as a digit-sharded operand, so each device
    quantizes ``x`` (replicated over the digit axis — DP axes stay auto)
    and reduces by ITS moduli only.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.quantize import quantize_with_scale

    def body(xb, sb, m_local):
        q = quantize_with_scale(xb, sb, bits)
        mv = m_local.reshape((-1,) + (1,) * q.ndim)
        res = jnp.remainder(q[None], mv)
        return res.astype(jnp.int8) if p.int8_safe else res

    return _jit_shard_map(
        body, ds,
        (P(*([None] * xndim)), P(*([None] * sndim)), P(ds.axis)),
        ds.digit_spec(xndim + 1))


def _sharded_convert(p, x, scale, bits, ds):
    x = jnp.asarray(x)
    scale = jnp.asarray(scale, jnp.float32)
    fn = _sharded_convert_fn(ds, p, bits, x.ndim, scale.ndim)
    return fn(x, scale, _moduli_arr(p))


@functools.lru_cache(maxsize=512)
def _sharded_matmul_fn(ds, p, andim, bndim):
    """Digit-sliced modular matmul, each device's digit group local.

    The body is ``rns_matmul_res``'s lazy-reduction schedule
    (``core/rns_matmul.modular_matmul`` — ONE source of truth for the
    overflow-critical chunking; the bound depends only on max(moduli),
    identical for every shard) with the moduli as a sharded operand.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.rns_matmul import modular_matmul

    chunk = p.lazy_chunk

    def body(a, b, m_local):
        mv = m_local.reshape((-1,) + (1,) * (a.ndim - 1))
        return modular_matmul(a, b, mv, chunk)

    return _jit_shard_map(
        body, ds,
        (ds.digit_spec(andim), ds.digit_spec(bndim), P(ds.axis)),
        ds.digit_spec(andim))


def _sharded_matmul(p, a_res, b_res, ds):
    fn = _sharded_matmul_fn(ds, p, a_res.ndim, b_res.ndim)
    return fn(a_res, b_res, _moduli_arr(p))


@functools.lru_cache(maxsize=512)
def _sharded_normalize_fn(ds, p, ndim, inv_scale, dtype):
    """MRC normalization: THE point where digit slices communicate.

    One tiled all-gather reassembles the full ``[K, ...]`` residue tensor
    on every device, then the sequential mixed-radix conversion runs
    replicated.  This is the paper's Fig. 5 topology as collectives: the
    PAC array never talks, the normalization unit is the meeting point.
    (Scattering the MRC over batch via all-to-all is a future refinement;
    it trades the replicated O(K^2) work for divisibility constraints.)

    On a mesh with a real (size > 1) auto axis — the DP x digit
    composition — the all-gather cannot live inside shard_map: XLA's
    SPMD partitioner (0.4.x) hard-crashes on manual-subgroup collectives
    mixed with auto axes.  There the digit gather is expressed as a
    GSPMD replication constraint on the digit axis instead (other dims
    unconstrained, so the MRC itself stays data-parallel over the batch).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import mrc

    if any(ds.mesh.shape[a] > 1 for a in ds.auto_axes()):
        def gather_and_decode(r):
            full = jax.lax.with_sharding_constraint(
                r, NamedSharding(
                    ds.mesh, P(None, *([P.UNCONSTRAINED] * (ndim - 1)))))
            return mrc.decode_float(p, full, inv_scale=inv_scale,
                                    dtype=dtype)

        return jax.jit(gather_and_decode)

    def body(r):
        full = jax.lax.all_gather(r, ds.axis, axis=0, tiled=True)
        return mrc.decode_float(p, full, inv_scale=inv_scale, dtype=dtype)

    return _jit_shard_map(body, ds, ds.digit_spec(ndim),
                          P(*([None] * (ndim - 1))))


def _sharded_normalize(p, res, inv_scale, dtype, ds):
    fn = _sharded_normalize_fn(ds, p, res.ndim, float(inv_scale),
                               jnp.dtype(dtype))
    return fn(res)


# ---------------------------------------------------------- primitives ----
def convert(profile, x, scale, *, bits: int = 16, backend: str | None = None,
            weight: bool = False):
    """Quantize ``x`` by ``scale`` and encode to residues [K, ...].

    Returns int8 digit planes when the profile is int8-safe (the Pallas
    matmul kernel's operand dtype), else int32.  ``weight=True`` marks
    the conversion of a static weight operand (tally bookkeeping only —
    the computation is identical); the resident-weight path eliminates
    exactly these.
    """
    from repro.core.moduli import get_profile

    _tally("converts")
    if weight:
        _tally("weight_converts")
    be = resolve_backend(backend)
    be = _FUSED_TO_UNFUSED.get(be, be)
    ds, p = _digit_ctx(profile)
    if p is None:
        p = get_profile(profile) if isinstance(profile, str) else profile
    if ds is not None:
        out = _sharded_convert(p, x, scale, bits, ds)
    elif be == "reference":
        # per-sequence grids (mask-aware absmax, non-scalar scales) run
        # through the Pallas kernel too since the scale became a streamed
        # operand — the old silent reference fallback is gone
        from repro.core.quantize import quantize_with_scale
        from repro.core.rns import encode_int32

        res = encode_int32(p, quantize_with_scale(x, scale, bits))
        out = res.astype(jnp.int8) if p.int8_safe else res
    else:
        from repro.kernels.rns_convert.ops import rns_convert

        out_dtype = jnp.int8 if p.int8_safe else jnp.int32
        out = rns_convert(p.name, x, scale, bits=bits,
                          interpret=_interpret_for(be), out_dtype=out_dtype)
    _emit("convert", out, (x,), profile=p.name, bits=bits, weight=weight,
          backend=be, sharded=ds is not None,
          tallies={"converts": 1, "weight_converts": int(weight)})
    return out


def matmul(profile, a_res, b_res, *, backend: str | None = None):
    """Digit-sliced modular matmul: [K,...,M,D] @ [K,D,N] -> [K,...,M,N]."""
    _tally("matmuls")
    be = resolve_backend(backend)
    be = _FUSED_TO_UNFUSED.get(be, be)
    ds, p = _digit_ctx(profile)
    if ds is not None:
        out = _sharded_matmul(p, a_res, b_res, ds)
    elif be == "reference":
        from repro.core.rns_matmul import rns_matmul_res

        out = rns_matmul_res(profile, a_res, b_res)
    else:
        from repro.kernels.rns_matmul.ops import rns_matmul

        out = rns_matmul(profile, a_res, b_res, interpret=_interpret_for(be))
    _emit("matmul", out, (a_res, b_res), profile=_prof_name(profile),
          contract_dim=int(jnp.shape(a_res)[-1]), backend=be,
          sharded=ds is not None, tallies={"matmuls": 1})
    return out


def normalize(profile, res, *, inv_scale: float = 1.0,
              backend: str | None = None, dtype=jnp.float32):
    """MRC-normalize residues to signed floats times ``inv_scale``.

    THE slow op (O(K^2) sequential digit steps).  ``inv_scale`` must be a
    static python float: the reference path folds it into the host-side
    float64 reconstruction weights, which keeps huge scale factors (e.g.
    M_f powers beyond float32 range) exact.  Traced scale factors must be
    multiplied in by the caller afterwards.
    """
    _tally("normalizes")
    be = resolve_backend(backend)
    be = _FUSED_TO_UNFUSED.get(be, be)
    ds, p = _digit_ctx(profile)
    if ds is not None:
        out = _sharded_normalize(p, res, inv_scale, dtype, ds)
    else:
        # the Pallas kernel reconstructs unscaled values; scales outside
        # the float32 range (deep M_f^frac_exp deferral) would under/
        # overflow the post-multiply, so those decodes take the reference
        # path — visibly (the fallback counter), not masquerading as a
        # pallas op
        if be != "reference" and not _inv_scale_in_f32(inv_scale):
            _tally_fallback("inv_scale outside float32 range")
            be = "reference"
        if be == "reference":
            from repro.core import mrc

            out = mrc.decode_float(profile, res, inv_scale=inv_scale,
                                   dtype=dtype)
        else:
            from repro.kernels.rns_normalize.ops import rns_normalize

            out = rns_normalize(profile, res, interpret=_interpret_for(be))
            if inv_scale != 1.0:
                out = out * jnp.asarray(inv_scale, out.dtype)
            out = out.astype(dtype)
    _emit("normalize", out, (res,), profile=_prof_name(profile), backend=be,
          sharded=ds is not None, tallies={"normalizes": 1})
    return out


# ------------------------------------------------- fused composites ----
def _inv_scale_in_f32(inv_scale: float) -> bool:
    return inv_scale == 1.0 or (2.0**-126 <= abs(inv_scale) <= 2.0**127)


def _fused_scale_ok(x, scale) -> bool:
    """Fused kernels take at most one scale per activation ROW: a scalar,
    or a keepdims shape with a broadcast last dim (per-sequence grids)."""
    if jnp.ndim(scale) == 0:
        return True
    xs, ss = jnp.shape(x), jnp.shape(scale)
    return (len(ss) == len(xs) and ss[-1] == 1
            and all(a in (1, b) for a, b in zip(ss, xs)))


def _get_p(profile):
    from repro.core.moduli import get_profile

    return get_profile(profile) if isinstance(profile, str) else profile


def fused_encode_matmul(profile, x, scale, w_res, *, bits: int = 16,
                        backend: str | None = None):
    """Forward conversion fused into the digit matmul.

    ``x [..., D]`` floats + ``w_res [K, D, N]`` weight residues ->
    ``[K, ..., N]`` residues; the activation residues never materialize
    in HBM.  Tallies one convert + one matmul (the logical ops are still
    performed) plus one ``fused``.  Decomposes into the separate
    primitives when the backend is not fused, when a digit-sharding
    context routes through shard_map, or when the scale is not row-
    foldable — the latter downgrades count as ``fallbacks``.
    """
    be = resolve_backend(backend)
    ds, p = _digit_ctx(profile)
    if p is None:
        p = _get_p(profile)
    fuse = ds is None and be in _FUSED_TO_UNFUSED
    if fuse and not _fused_scale_ok(x, scale):
        _tally_fallback("non-row-foldable scale")
        fuse = False
    if not fuse:
        ub = _FUSED_TO_UNFUSED.get(be, be)
        res = convert(p, x, scale, bits=bits, backend=ub)
        return matmul(p, res, w_res, backend=ub)
    _tally("converts")
    _tally("matmuls")
    _tally("fused")
    from repro.kernels.rns_fused.ops import rns_fused_encode_matmul

    out = rns_fused_encode_matmul(p, x, scale, w_res, bits=bits,
                                  interpret=_interpret_for(be))
    _emit("fused_encode_matmul", out, (x, w_res), profile=p.name, bits=bits,
          contract_dim=int(jnp.shape(x)[-1]), backend=be,
          tallies={"converts": 1, "matmuls": 1, "fused": 1})
    return out


def fused_matmul_normalize(profile, a_res, b_res, *, inv_scale: float = 1.0,
                           backend: str | None = None, dtype=jnp.float32):
    """Digit matmul fused with THE MRC normalization.

    ``a_res [K, ..., D]`` @ ``b_res [K, D, N]`` -> ``[..., N]`` floats
    times ``inv_scale``; the [K, ..., N] int32 accumulator never reaches
    HBM.  Tallies one matmul + one normalize plus one ``fused``.
    """
    be = resolve_backend(backend)
    ds, p = _digit_ctx(profile)
    if p is None:
        p = _get_p(profile)
    fuse = ds is None and be in _FUSED_TO_UNFUSED
    # an out-of-range inv_scale decomposes WITHOUT tallying a fallback
    # here: normalize() itself records the visible downgrade
    fuse = fuse and _inv_scale_in_f32(inv_scale)
    if not fuse:
        ub = _FUSED_TO_UNFUSED.get(be, be)
        res = matmul(p, a_res, b_res, backend=ub)
        return normalize(p, res, inv_scale=inv_scale, backend=ub, dtype=dtype)
    _tally("matmuls")
    _tally("normalizes")
    _tally("fused")
    from repro.kernels.rns_fused.ops import rns_fused_matmul_normalize

    out = rns_fused_matmul_normalize(p, a_res, b_res,
                                     interpret=_interpret_for(be))
    if inv_scale != 1.0:
        out = out * jnp.asarray(inv_scale, out.dtype)
    out = out.astype(dtype)
    _emit("fused_matmul_normalize", out, (a_res, b_res), profile=p.name,
          contract_dim=int(jnp.shape(a_res)[-1]), backend=be,
          tallies={"matmuls": 1, "normalizes": 1, "fused": 1})
    return out


def fused_dot(profile, x, scale, w_res, *, bits: int = 16,
              inv_scale: float = 1.0, backend: str | None = None,
              dtype=jnp.float32, shared_encode: bool = False):
    """The whole Fig. 5 pipeline in one kernel: encode -> digit matmul ->
    MRC normalize.  Floats in, floats out (times ``inv_scale``); residues
    only ever exist in VMEM.  Tallies convert + matmul + normalize plus
    one ``fused``.

    ``shared_encode``: the activation's forward conversion is logically
    shared with a previous composite over the same ``x`` in this
    expression (``rns_multi_dot``'s one-conversion-per-block contract) —
    the kernel still re-quantizes in VMEM (free vs HBM), but the
    structural ``converts`` tally stays backend-independent."""
    be = resolve_backend(backend)
    ds, p = _digit_ctx(profile)
    if p is None:
        p = _get_p(profile)
    fuse = ds is None and be in _FUSED_TO_UNFUSED
    if fuse and not _fused_scale_ok(x, scale):
        _tally_fallback("non-row-foldable scale")
        fuse = False
    fuse = fuse and _inv_scale_in_f32(inv_scale)   # normalize() tallies
    if not fuse:
        ub = _FUSED_TO_UNFUSED.get(be, be)
        res = convert(p, x, scale, bits=bits, backend=ub)
        out = matmul(p, res, w_res, backend=ub)
        return normalize(p, out, inv_scale=inv_scale, backend=ub, dtype=dtype)
    if not shared_encode:
        _tally("converts")
    _tally("matmuls")
    _tally("normalizes")
    _tally("fused")
    from repro.kernels.rns_fused.ops import rns_fused_dot

    out = rns_fused_dot(p, x, scale, w_res, bits=bits,
                        interpret=_interpret_for(be))
    if inv_scale != 1.0:
        out = out * jnp.asarray(inv_scale, out.dtype)
    out = out.astype(dtype)
    _emit("fused_dot", out, (x, w_res), profile=p.name, bits=bits,
          contract_dim=int(jnp.shape(x)[-1]), backend=be,
          shared_encode=shared_encode,
          tallies={"converts": 0 if shared_encode else 1, "matmuls": 1,
                   "normalizes": 1, "fused": 1})
    return out
