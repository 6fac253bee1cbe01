"""Serving engines: bucketed batching (legacy) and continuous batching.

:class:`Engine` is the original bucketed engine — batches grouped by
exact prompt length, run to completion.  It remains as the baseline the
traffic benchmark compares against (and for equal-length workloads where
its simplicity wins).

:class:`ContinuousEngine` is the production path: a paged KV cache
(``serve/kv_cache.py``) plus a host-side scheduler
(``serve/scheduler.py``) admit and evict sequences *mid-decode*.  Mixed
prompt lengths share

  * ONE jitted prefill (prompts right-padded to ``prompt_pad``; per-row
    lengths make the padding inert), and
  * ONE jitted decode step (shapes depend only on the slot count and the
    page geometry — never on a prompt length),

so serving arbitrary traffic costs zero per-length recompiles.  Finished
rows free their pages the same step (slot compaction); when the page
pool runs dry the scheduler preempts the youngest sequence and
re-prefills it later (recompute preemption — greedy decode makes the
replay identical).

RNS execution policy: as in the bucketed engine, ``rns_backend`` /
``rns_defer`` override the model config (serving is forward-only, so
residue-domain deferral is free), prefill reuses the shared forward
conversion + deferred-MLP chain, and each ``step()`` reports the
structural convert/matmul/normalize tallies it scheduled
(``stats["rns_ops"]``).  Ragged prefill and batched decode are
token-identical to solo runs on the RNS path too: per-sequence
quantization grids (``core/quantize.token_mask``) keep each row's
fixed-point scale independent of its neighbours and of pad garbage.
With ``ServeConfig.mesh`` set, the whole RNS datapath runs
digit-sharded over the mesh's ``model`` axis (see docs/distributed.md).
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch
from repro.models import model as M
from repro.serve import kv_cache as kv
from repro.serve.scheduler import Request, Scheduler
from repro.serve.trace import Tracer


@dataclasses.dataclass
class ServeConfig:
    """Engine knobs shared by both engines.

    ``eos_id`` semantics: a *non-negative* value is the vocabulary id that
    stops a row's generation; the special sentinel ``-1`` means "never
    stop early" (synthetic-traffic benchmarks, perplexity sweeps).  Any
    other negative value can silently never match a sampled token, so it
    is rejected at construction time.
    """

    max_cache: int = 512
    max_new_tokens: int = 32
    eos_id: int = -1            # -1 sentinel: never stops early
    cache_dtype: str = "float32"
    # RNS execution policy overrides (None: keep the model config's).
    # "pallas_fused" routes the whole datapath — including ragged prefill
    # with its per-sequence quantization grids — through the composite
    # kernels (kernels/rns_fused); step stats gain nonzero rns_ops.fused.
    rns_backend: str | None = None   # see core/dispatch.BACKENDS | auto
    rns_defer: bool | None = None    # residue-domain MLP chaining
    # resident residue-domain weights: encode every RNS-target MLP weight
    # ONCE at engine build (models/resident.encode_resident) so the
    # per-step jits consume pre-encoded residues — weight conversions drop
    # to zero while the token stream stays bit-identical to re-encode.
    resident_weights: bool = False
    # per-layer moduli profiles (requires resident_weights): narrow layers
    # are encoded on fewer/smaller moduli, chosen from quantized-weight
    # column-sum statistics with a magnitude-ledger exactness proof.
    per_layer_profiles: bool = False
    # residue-channel sharding: a jax Mesh whose ``digit_axis`` partitions
    # the RNS digit axis (one group of moduli per device; digits meet only
    # at MRC normalization).  None: single-device layout, unchanged.
    mesh: object | None = None       # jax.sharding.Mesh
    digit_axis: str = "model"
    # continuous batching / paged cache (ContinuousEngine only)
    page_size: int = 16              # tokens per physical page
    max_seqs: int = 8                # concurrent decode slots
    n_pages: int | None = None       # physical pool (None: max_seqs full seqs)
    prompt_pad: int | None = None    # prefill pad length (None: seq capacity)
    # copy-on-write prefix caching: sequences sharing a prompt prefix map
    # their block tables onto shared pages (content-addressed index in
    # serve/kv_cache.PrefixCache); the prefill blit skips shared blocks
    # (zero redundant page writes) and a row splits a shared page the
    # first time it writes into one (COW).
    prefix_cache: bool = False
    # self-speculative (n-gram / prompt-lookup) decoding: each step scores
    # [last_token, draft_1..draft_k] through ONE jitted [R, k+1] verify
    # call; greedy accept/reject keeps the stream token-identical to
    # vanilla decode while emitting up to k+1 tokens per step.
    spec_decode: bool = False
    spec_k: int = 3                  # draft tokens per step (window = k+1)
    spec_ngram: int = 3              # max n-gram length for prompt lookup
    # chunked prefill + packed mixed-phase batching (ContinuousEngine):
    # ONE jitted step consumes up to ``token_budget`` packed lanes per
    # iteration — decode rows (spec_k+1 lanes each when spec_decode is
    # on) and prefill chunks of at most ``chunk_size`` tokens (None: no
    # per-row cap beyond the budget).  While any row is prefilling,
    # ``prefill_reserve`` lanes are reserved for chunks (None: half the
    # budget), bounding time-to-first-token under decode load.
    chunked_prefill: bool = False
    token_budget: int = 64           # packed lanes per mixed step
    chunk_size: int | None = None    # max prefill tokens per row per step
    prefill_reserve: int | None = None   # lanes reserved for chunks
    # sliding-window attention + cyclic KV page reuse: each row retains
    # at most ``window_tokens`` of context (query q attends keys
    # [q - window + 1, q], exact-zero masking below that) and the
    # scheduler recycles the oldest full pages as rows outgrow the
    # window — physical occupancy stays bounded by the window no matter
    # how long the stream runs.  Positions stay absolute, so the stream
    # is token-identical to a solo run with the same window.  Windowed
    # rows never register prefix-cache blocks (every block is eventually
    # evicted; the index only holds immutable live pages).
    window_tokens: int | None = None
    # exactness audit at engine build: run the static magnitude-ledger
    # auditor (repro.analysis.ledger_audit) over every jitted phase this
    # config will serve and REFUSE to construct an engine whose RNS
    # datapath cannot be proven overflow-free.  The report is kept on
    # ``engine.audit_report``.  No-op for float configs (cfg.rns None).
    audit: bool = False

    def __post_init__(self):
        if self.eos_id < -1:
            raise ValueError(
                f"eos_id={self.eos_id}: vocabulary ids are non-negative; "
                "use a valid token id, or -1 (the documented sentinel) to "
                "disable early stopping")
        if self.spec_decode and self.spec_k < 1:
            raise ValueError(
                f"spec_k={self.spec_k}: speculative decoding needs at "
                "least one draft token per step")
        if self.spec_decode and self.spec_ngram < 1:
            raise ValueError(f"spec_ngram={self.spec_ngram}: need >= 1")
        if self.per_layer_profiles and not self.resident_weights:
            raise ValueError(
                "per_layer_profiles selects moduli at weight-encode time; "
                "it requires resident_weights=True")
        # cross-feature coherence for the chunked mixed step: every
        # incoherent combination is named by the fields that conflict.
        if self.chunked_prefill:
            if self.token_budget < 1:
                raise ValueError(
                    f"token_budget={self.token_budget}: chunked_prefill "
                    "needs at least one packed lane per step")
            if self.spec_decode and self.token_budget < self.spec_k + 1:
                raise ValueError(
                    f"token_budget={self.token_budget} < spec_k+1="
                    f"{self.spec_k + 1}: a speculative decode row needs "
                    "spec_k+1 lanes in one mixed step; raise token_budget "
                    "or lower spec_k")
            if self.cache_dtype != "float32":
                raise ValueError(
                    f"cache_dtype={self.cache_dtype!r}: chunked prefill "
                    "re-reads earlier chunks' KV from the page pool, so "
                    "the cache must be lossless (float32) to stay "
                    "token-identical to whole-prompt prefill")
        if self.chunk_size is not None:
            if not self.chunked_prefill:
                raise ValueError(
                    "chunk_size is only meaningful with "
                    "chunked_prefill=True")
            if self.chunk_size < 1:
                raise ValueError(f"chunk_size={self.chunk_size}: need >= 1")
            if self.chunk_size % self.page_size:
                raise ValueError(
                    f"chunk_size={self.chunk_size} is not a multiple of "
                    f"page_size={self.page_size}: chunk boundaries must "
                    "align with KV pages so completed blocks register "
                    "with the prefix cache as chunks land")
            if self.chunk_size > self.token_budget:
                raise ValueError(
                    f"chunk_size={self.chunk_size} > token_budget="
                    f"{self.token_budget}: a chunk can never exceed the "
                    "packed lanes available in one step")
        if self.prefill_reserve is not None:
            if not self.chunked_prefill:
                raise ValueError(
                    "prefill_reserve is only meaningful with "
                    "chunked_prefill=True")
            if not 0 <= self.prefill_reserve < self.token_budget:
                raise ValueError(
                    f"prefill_reserve={self.prefill_reserve}: must be in "
                    f"[0, token_budget={self.token_budget}) so decode "
                    "rows keep making progress")
        if self.window_tokens is not None and self.window_tokens < 1:
            raise ValueError(
                f"window_tokens={self.window_tokens}: a sliding window "
                "must retain at least the current token (use None for "
                "full attention)")


def _with_digit_ctx(fn, scfg: ServeConfig):
    """Wrap a jitted callable so tracing sees the digit-sharding context.

    The context only matters during the (first-call) trace, where
    ``core/dispatch.py`` routes convert/matmul/normalize through the
    per-device shard_map bodies; afterwards the wrapper is a cheap
    passthrough.  ``_cache_size`` is forwarded — tests pin the
    zero-per-length-recompiles contract through it.
    """
    if scfg.mesh is None:
        return fn
    from repro.distributed.sharding import use_digit_sharding

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with use_digit_sharding(scfg.mesh, scfg.digit_axis):
            return fn(*args, **kwargs)

    if hasattr(fn, "_cache_size"):
        wrapped._cache_size = fn._cache_size
    return wrapped


def _apply_rns_policy(model_cfg, scfg: ServeConfig):
    """Fold the serve-side execution overrides into the model config:
    RNS backend/defer policy, and the sliding-window width (which both
    engines thread to attention as ``cfg.attn_window`` — the solo
    bucketed engine with the same ``window_tokens`` is the reference the
    continuous stream is token-identical to)."""
    if scfg.window_tokens is not None:
        model_cfg = dataclasses.replace(model_cfg,
                                        attn_window=scfg.window_tokens)
    if model_cfg.rns is None or (
            scfg.rns_backend is None and scfg.rns_defer is None):
        return model_cfg
    rns = model_cfg.rns
    if scfg.rns_backend is not None:
        rns = dataclasses.replace(rns, backend=scfg.rns_backend)
    if scfg.rns_defer is not None:
        rns = dataclasses.replace(rns, defer=scfg.rns_defer)
    return dataclasses.replace(model_cfg, rns=rns)


def _maybe_resident(params, cfg, scfg: ServeConfig):
    """Encode resident weights at engine build time when asked to."""
    if not scfg.resident_weights or cfg.rns is None:
        return params
    from repro.models.resident import encode_resident

    return encode_resident(params, cfg,
                           per_layer_profiles=scfg.per_layer_profiles,
                           mesh=scfg.mesh, digit_axis=scfg.digit_axis)


def _maybe_audit(engine):
    """Build-time static audits (``ServeConfig(audit=True)``).

    Three ahead-of-time proofs, in order, each refusing to hand back the
    engine with the failed report's summary as the exception text:

    1. **exactness audit** (``repro.analysis.ledger_audit``) — the RNS
       datapath is provably overflow-free; kept on (and returned as)
       ``engine.audit_report``.  Float configs have nothing to prove
       ledger-wise and keep ``audit_report is None``.  This runs FIRST
       so a numerically unprovable config is named by the exactness
       pass — its ledger error would otherwise abort the kernel
       capture below and be misblamed as a launch failure;
    2. **trace audit** (``repro.analysis.trace_audit``) — every jitted
       phase's cache key is traffic-invariant (no steady-state
       recompiles); kept on ``engine.trace_audit_report``;
    3. **kernel audit** (``repro.analysis.kernel_audit``) — every Pallas
       launch the phases lower to is Mosaic-legal and within the VMEM
       budget (an illegal tuned block config refuses to build here);
       kept on ``engine.kernel_audit_report``.
    """
    engine.trace_audit_report = None
    engine.kernel_audit_report = None
    if not engine.scfg.audit:
        return None
    from repro.analysis.kernel_audit import audit_engine_kernels
    from repro.analysis.trace_audit import audit_traces

    report = None
    if engine.cfg.rns is not None:
        from repro.analysis.kernel_audit import BlockConfigError
        from repro.analysis.ledger_audit import audit_engine

        try:
            report = audit_engine(engine)
        except BlockConfigError:
            # an illegal tile aborts the exactness trace; fall through —
            # the kernel audit below reproduces and names it properly
            report = None
        else:
            if not report.ok:
                raise ValueError("ServeConfig(audit=True): exactness "
                                 "audit failed\n" + report.summary())
    trace_report = audit_traces(engine)
    engine.trace_audit_report = trace_report
    if not trace_report.ok:
        raise ValueError("ServeConfig(audit=True): trace audit failed\n"
                         + trace_report.summary())
    kernel_report = audit_engine_kernels(engine)
    engine.kernel_audit_report = kernel_report
    if not kernel_report.ok:
        raise ValueError("ServeConfig(audit=True): kernel audit failed\n"
                         + kernel_report.summary())
    return report


class Engine:
    """Bucketed batching: equal-length prompts, batch runs to completion."""

    def __init__(self, params, model_cfg, scfg: ServeConfig):
        self.cfg = _apply_rns_policy(model_cfg, scfg)
        self.params = _maybe_resident(params, self.cfg, scfg)
        self.scfg = scfg
        self._prefill = _with_digit_ctx(jax.jit(
            functools.partial(M.prefill, cfg=self.cfg, S_max=scfg.max_cache,
                              cache_dtype=jnp.dtype(scfg.cache_dtype)),
            static_argnames=()), scfg)
        self._decode = _with_digit_ctx(jax.jit(
            lambda params, tok, cache: M.decode_step(
                params, self.cfg, tok, cache)), scfg)
        self.audit_report = _maybe_audit(self)

    def rns_op_counts(self, B: int = 1, T: int = 8) -> dispatch.OpCounts:
        """Structural RNS primitive counts for one [B, T] prefill trace."""
        batch = {"tokens": jnp.zeros((B, T), jnp.int32)}
        return dispatch.trace_op_counts(
            lambda p, b: M.prefill(p, self.cfg, b, S_max=self.scfg.max_cache),
            self.params, batch)

    def _trace_specs(self, traffic: dict | None = None) -> dict:
        """``{phase: (fn, args)}`` for the static auditors
        (repro.analysis.ledger_audit / kernel_audit / trace_audit).  The
        bucketed engine serves one compound program — prefill then
        decode on the returned cache — so one combined phase covers both
        jits.  ``traffic`` varies the token *values* only: this engine
        recompiles per (B, T) bucket BY DESIGN, so its invariance claim
        (and the trace audit's proof) is scoped to one bucket."""
        fill = int((traffic or {}).get("fill", 0))

        def prefill_decode(p, t):
            logits, cache = M.prefill(p, self.cfg, {"tokens": t},
                                      S_max=self.scfg.max_cache)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            return M.decode_step(p, self.cfg, tok, cache)

        return {"prefill+decode": (
            prefill_decode, (self.params, jnp.full((1, 8), fill, jnp.int32)))}

    def generate(self, prompts: np.ndarray, frontend: np.ndarray | None = None,
                 max_new: int | None = None):
        """prompts [B, T] int32 (equal lengths). Returns [B, n_new] tokens."""
        max_new = max_new or self.scfg.max_new_tokens
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if frontend is not None:
            batch["frontend"] = jnp.asarray(frontend)
        logits, cache = self._prefill(self.params, batch=batch)
        B = prompts.shape[0]
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        done = jnp.zeros((B,), bool)
        out = [tok]
        for _ in range(max_new - 1):
            done = done | (tok[:, 0] == self.scfg.eos_id)
            logits, cache = self._decode(self.params, tok, cache)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            tok = jnp.where(done[:, None], tok, nxt)
            out.append(tok)
            if bool(jnp.all(done)):
                break
        return np.asarray(jnp.concatenate(out, axis=1))


# ------------------------------------------------------------ continuous ---
def _round_up(n: int, k: int) -> int:
    return -(-n // k) * k


class ContinuousEngine:
    """In-flight batching over a paged KV cache (decoder-only attn/mla)."""

    def __init__(self, params, model_cfg, scfg: ServeConfig):
        cfg = _apply_rns_policy(model_cfg, scfg)
        bad = sorted({t for t in cfg.layer_types if t not in ("attn", "mla")})
        if bad:
            raise NotImplementedError(
                f"continuous batching pages attn/mla caches only; "
                f"{cfg.arch_id} has layer types {bad}")
        if cfg.enc_dec or cfg.frontend is not None:
            raise NotImplementedError(
                "continuous batching is decoder-only (no enc-dec / frontend)")
        if not cfg.causal:
            raise NotImplementedError("continuous batching requires causal "
                                      "attention (padded prefill relies on it)")
        self.params = _maybe_resident(params, cfg, scfg)
        self.cfg = cfg
        self.scfg = scfg

        bs = scfg.page_size
        max_blocks = -(-scfg.max_cache // bs)
        n_pages = scfg.n_pages or 1 + scfg.max_seqs * max_blocks
        self.spec_window = scfg.spec_k + 1 if scfg.spec_decode else 1
        resident = None
        if scfg.window_tokens is not None:
            # window + lookahead tokens straddle at most this many pages
            resident = -(-(scfg.window_tokens + self.spec_window) // bs) + 1
        self.pcfg = kv.PagedCacheConfig(
            page_size=bs, n_pages=n_pages, max_seqs=scfg.max_seqs,
            max_blocks=max_blocks, resident_blocks=resident)
        self.prompt_pad = _round_up(
            scfg.prompt_pad or self.pcfg.tokens_per_seq, bs)
        if self.prompt_pad > self.pcfg.tokens_per_seq:
            raise ValueError(
                f"prompt_pad {self.prompt_pad} exceeds per-seq cache "
                f"capacity {self.pcfg.tokens_per_seq}")
        self.chunked = scfg.chunked_prefill
        if self.chunked and cfg.rns is not None and cfg.rns_targets == "all" \
                and "mla" in cfg.layer_types:
            raise NotImplementedError(
                "chunked_prefill with rns_targets='all' on an MLA model: "
                "packed chunk tokens re-expand gathered latents, and the "
                "original per-token quantization grids of earlier chunks "
                "are not recoverable from the cache; use rns_targets='mlp'")
        reserve = scfg.prefill_reserve
        if reserve is None:
            reserve = max(1, scfg.token_budget // 2)
        self.sched = Scheduler(self.pcfg, prefix_cache=scfg.prefix_cache,
                               lookahead=self.spec_window,
                               chunked=self.chunked,
                               token_budget=scfg.token_budget,
                               chunk_size=scfg.chunk_size,
                               prefill_reserve=reserve if self.chunked else 0,
                               window_tokens=scfg.window_tokens)
        self.cache = kv.make_paged_cache(
            cfg, self.pcfg, dtype=jnp.dtype(scfg.cache_dtype))

        self._prefill = _with_digit_ctx(jax.jit(
            lambda params, tokens, lengths: M.prefill_ragged(
                params, self.cfg, {"tokens": tokens}, lengths)), scfg)

        def _decode_fn(params, tok, cache, active):
            logits, cache = M.decode_step(params, self.cfg, tok, cache,
                                          active=active)
            # argmax on device: the host pulls R ints, not R x vocab logits
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        # donate the cache operand: the page pool is the big allocation,
        # and both callers immediately rebind self.cache to the result —
        # without donation every decoded token copies the whole pool
        self._decode = _with_digit_ctx(
            jax.jit(_decode_fn, donate_argnums=(2,)), scfg)
        # ONE jitted [R, W] verify step replaces the [R, 1] decode when
        # speculative decoding is on — same zero-per-length-recompiles
        # contract (shapes depend only on the slot count, the page
        # geometry, and the static window width)
        self._verify = _with_digit_ctx(
            jax.jit(self._verify_fn, donate_argnums=(2,)), scfg)
        self._cow = jax.jit(self._cow_fn, donate_argnums=(0,))
        self._ingest = jax.jit(self._ingest_fn, donate_argnums=(0,))
        # ONE jitted mixed step: every iteration consumes the same fixed
        # [token_budget] packed lanes regardless of how many chunks vs
        # decode rows fill them, so the phase mix never recompiles
        self._mixed = _with_digit_ctx(
            jax.jit(self._mixed_fn, donate_argnums=(6,)), scfg)
        self._tables_dirty = True
        self._active = np.zeros((self.pcfg.max_seqs,), bool)

        self._next_rid = 0
        self._step_idx = 0
        self.results: dict[int, np.ndarray] = {}
        self.latencies: dict[int, float] = {}    # submit -> finish, seconds
        self.ttfts: dict[int, float] = {}        # submit -> first token
        self._op_cache: dict[str, dispatch.OpCounts] = {}
        # in-program spans and request events (serve/trace.py); off
        # unless a caller swaps in ``Tracer(on=True)``
        self.tracer = Tracer()
        self.audit_report = _maybe_audit(self)

    # ----------------------------------------------------------- ingest ---
    def _ingest_fn(self, cache, ys, block_row):
        """Blit one prefilled request's KV planes into its pages."""
        new = dict(cache)
        for j in range(self.cfg.period):
            lt = self.cfg.layer_types[j]
            z = dict(cache[f"l{j}"])
            y = ys[f"l{j}"]
            if lt == "attn":
                k, v = y
                z["k_pages"] = kv.write_prompt_pages(z["k_pages"], block_row, k)
                z["v_pages"] = kv.write_prompt_pages(z["v_pages"], block_row, v)
            else:  # mla
                ckv, krope = y
                z["ckv_pages"] = kv.write_prompt_pages(
                    z["ckv_pages"], block_row, ckv)
                z["krope_pages"] = kv.write_prompt_pages(
                    z["krope_pages"], block_row, krope)
            new[f"l{j}"] = z
        return new

    def _verify_fn(self, params, window, cache, active, caps):
        """Score a [R, W] draft window and accept/reject on device.

        ``window[:, 0]`` is each row's last emitted token, ``window[:,
        1:]`` its drafts.  Greedy accept: draft i+1 survives iff it
        equals the argmax after window position i AND every earlier draft
        survived — so the emitted stream is the model's own greedy chain
        by construction, token-identical to vanilla decode.  ``caps``
        bounds acceptance per row (max_new budget; drafts whose KV landed
        on the trash page).  Cache lengths advance by accepted+1 on
        device, keeping them in lockstep with the host counters so the
        table upload stays skippable.

        Returns (greedy [R, W], accepted [R], cache).
        """
        logits, ys = M.decode_window(params, self.cfg, window, cache,
                                     active=active)
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # [R, W]
        match = (g[:, :-1] == window[:, 1:]).astype(jnp.int32)  # [R, W-1]
        a = jnp.minimum(jnp.sum(jnp.cumprod(match, axis=1), axis=1), caps)
        step = jnp.where(active, a + 1, 0)
        new_cache = M.set_cache_lengths(ys, M._cache_lengths(ys) + step)
        return g, a, new_cache

    def _mixed_fn(self, params, tokens, seg, pos, dec, valid, cache):
        """One packed mixed-phase step over [token_budget] lanes.

        Each lane is (token, owning slot, absolute position, is-decode,
        is-valid); prefill chunks and decode/spec windows share the one
        program.  Returns per-lane greedy argmaxes — the host walks the
        segment map to turn them into first tokens (chunk tails) or
        accept decisions (spec windows).
        """
        logits, cache = M.mixed_step(params, self.cfg, tokens, seg, pos,
                                     dec, valid, cache)
        with jax.named_scope("logits"):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    def _cow_fn(self, cache, src, dst):
        """Copy-on-write page duplication across every layer's pool."""
        new = dict(cache)
        for j in range(self.cfg.period):
            z = dict(cache[f"l{j}"])
            for name in list(z):
                if name.endswith("_pages"):
                    z[name] = kv.copy_pages(z[name], src, dst)
            new[f"l{j}"] = z
        return new

    def _apply_cow(self, cow):
        """Run the scheduler's COW splits on device (before decode writes).

        Fixed [R] src/dst vectors (TRASH for no-op rows) keep the copy
        jit at one compile; rounds handle the (rare) case of several
        splits on one slot.
        """
        R = self.pcfg.max_seqs
        while cow:
            this_round, rest, seen = [], [], set()
            for e in cow:
                if e[0] in seen:
                    rest.append(e)
                else:
                    seen.add(e[0])
                    this_round.append(e)
            src = np.full((R,), kv.TRASH_PAGE, np.int32)
            dst = np.full((R,), kv.TRASH_PAGE, np.int32)
            for slot, _b, s, d in this_round:
                src[slot], dst[slot] = s, d
            self.cache = self._cow(self.cache, jnp.asarray(src),
                                   jnp.asarray(dst))
            cow = rest

    def _propose(self, seq) -> np.ndarray:
        """Prompt-lookup (n-gram) drafting: match the row's trailing
        n-gram against its own prompt+generation history and propose the
        k tokens that followed the most recent earlier occurrence.
        Misses pad with zeros — a padded draft is only ever accepted if
        it happens to equal the model's greedy choice, so correctness
        never depends on draft quality."""
        k = self.scfg.spec_k
        hist = np.concatenate([seq.req.tokens,
                               np.asarray(seq.emitted, np.int32)])
        out = np.zeros((k,), np.int32)
        for n in range(min(self.scfg.spec_ngram, len(hist) - 1), 0, -1):
            pat = hist[-n:]
            base = hist[:-1]                 # candidate starts need a next
            if len(base) < n:
                continue
            wins = np.lib.stride_tricks.sliding_window_view(base, n)
            hits = np.flatnonzero((wins == pat).all(axis=1))
            if len(hits):
                j = int(hits[-1]) + n
                d = hist[j:j + k]
                out[: len(d)] = d
                return out
        return out

    # ------------------------------------------------------------ intake --
    def submit(self, prompt: np.ndarray, max_new: int | None = None) -> int:
        """Queue one request; returns its request id."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        max_new = max_new or self.scfg.max_new_tokens
        if not self.chunked and len(prompt) > self.prompt_pad:
            raise ValueError(
                f"prompt length {len(prompt)} > prompt_pad {self.prompt_pad}; "
                "raise ServeConfig.prompt_pad or turn on chunked_prefill")
        rid = self._next_rid
        self._next_rid += 1
        t = time.perf_counter()
        self.sched.submit(Request(rid=rid, tokens=prompt, max_new=max_new,
                                  submit_time=t))
        self.tracer.event(rid, "submit", t)
        return rid

    # ----------------------------------------------------------- stepping --
    def _do_prefill(self, seq):
        T = len(seq.req.tokens)
        tokens = np.zeros((1, self.prompt_pad), np.int32)
        tokens[0, :T] = seq.req.tokens
        logits, ys = self._prefill(self.params, jnp.asarray(tokens),
                                   jnp.asarray([T], jnp.int32))
        tok0 = int(jnp.argmax(logits, axis=-1)[0])
        nbp = self.prompt_pad // self.pcfg.page_size
        # block_row maps prefix-cache-shared blocks to the trash page:
        # their KV is already resident, the blit skips them entirely
        block_row = self.sched.block_row(seq, nbp)
        self.cache = self._ingest(self.cache, ys, jnp.asarray(block_row))
        self.sched.register_prefix(seq)
        seq.emitted = [tok0]
        seq.last_token = tok0
        now = time.perf_counter()
        ttft = now - seq.req.submit_time
        self.ttfts[seq.rid] = ttft
        self._step_ttfts.append(ttft)
        self.tracer.event(seq.rid, "first_token", now)
        # length stays at T: the decode step writes tok0's KV at position T

    def _finish(self, seq):
        self.results[seq.rid] = np.asarray(seq.emitted, np.int32)
        now = time.perf_counter()
        self.latencies[seq.rid] = now - seq.req.submit_time
        self.tracer.event(seq.rid, "finish", now)
        self.sched.complete(seq)
        self._tables_dirty = True

    def _trace_specs(self, traffic: dict | None = None) -> dict:
        """``{phase: (fn, args)}`` — every jitted shape this config serves.

        ONE source of truth shared by the per-step op counters (traced
        through ``dispatch.trace_op_counts``) and the static auditors
        (``repro.analysis``' ledger_audit / kernel_audit / trace_audit):
        whatever the engine would actually jit is exactly what gets
        audited.  The closures read ``self.cfg`` dynamically, so the
        auditor can probe policy variants (e.g. defer=True) by swapping
        it.  ``traffic`` (``{"fill": tok, "length": L}``) varies the
        argument *contents* the way real requests would — the trace
        auditor proves the resulting jit signatures don't.
        """
        tr = traffic or {}
        fill = int(tr.get("fill", 0))
        L = max(1, min(int(tr.get("length", 1)), self.prompt_pad))
        bt, lengths, active, last = self.sched.tables()
        cache = kv.set_tables(self.cache, bt, lengths)
        if self.chunked:
            # the mixed step's structure is phase-mix invariant: fixed
            # [token_budget] lanes, one trace serves every step
            zi = jnp.full((self.scfg.token_budget,), fill, jnp.int32)
            zb = jnp.zeros((self.scfg.token_budget,), bool)
            return {"mixed": (
                lambda p, t: M.mixed_step(p, self.cfg, t, zi, zi, zb,
                                          zb, cache),
                (self.params, zi))}
        R = self.pcfg.max_seqs
        if self.scfg.spec_decode:
            # spec mode replaces the decode step with the verify step
            decode = (
                lambda p, t: self._verify_fn(
                    p, t, cache, jnp.asarray(active),
                    jnp.zeros((R,), jnp.int32)),
                (self.params,
                 jnp.full((R, self.spec_window), fill, jnp.int32)))
        else:
            decode = (
                lambda p, t: M.decode_step(p, self.cfg, t, cache,
                                           active=jnp.asarray(active)),
                (self.params, jnp.full((R, 1), fill, jnp.int32)))
        # prompt tokens/lengths are jit ARGUMENTS (mirroring the runtime
        # ``self._prefill(params, tokens, [T])`` call), so ragged lengths
        # exercise the same compiled program — which is the claim the
        # trace auditor proves over the traffic family.
        tokens = np.zeros((1, self.prompt_pad), np.int32)
        tokens[0, :L] = fill
        prefill = (
            lambda p, t, n: M.prefill_ragged(
                p, self.cfg, {"tokens": t}, n),
            (self.params, jnp.asarray(tokens),
             jnp.asarray([L], jnp.int32)))
        return {"decode": decode, "prefill": prefill}

    def _rns_ops(self, n_prefills: int) -> dispatch.OpCounts:
        """Structural convert/matmul/normalize counts for this step."""
        if self.cfg.rns is None:
            return dispatch.OpCounts()
        if not self._op_cache:
            from repro.distributed.sharding import use_digit_sharding

            # count what the jits trace: under the engine's digit mesh the
            # composites decompose into the sharded primitives
            with use_digit_sharding(self.scfg.mesh, self.scfg.digit_axis):
                for name, (fn, args) in self._trace_specs().items():
                    self._op_cache[name] = dispatch.trace_op_counts(fn, *args)
        if self.chunked:
            return self._op_cache["mixed"]
        return self._op_cache["decode"].add(self._op_cache["prefill"],
                                            times=n_prefills)

    def _decode_vanilla(self, last):
        """One [R, 1] decode for every running row; returns #new tokens."""
        tr = self.tracer
        with tr.span("upload"):
            args = (jnp.asarray(last[:, None]), jnp.asarray(self._active))
        with tr.span("dispatch"):
            nxt, self.cache = self._decode(self.params, args[0], self.cache,
                                           args[1])
        with tr.span("readback"):
            nxt = np.asarray(nxt, np.int32)
        n_tokens = 0
        with tr.span("emit"):
            for seq in list(self.sched.running.values()):
                tok = int(nxt[seq.slot])
                seq.emitted.append(tok)
                seq.last_token = tok
                seq.length += 1
                n_tokens += 1
                if (len(seq.emitted) >= seq.req.max_new
                        or tok == self.scfg.eos_id
                        or seq.length + 1 > self.pcfg.tokens_per_seq):
                    self._step_finished.append(seq.rid)
                    self._finish(seq)
        return n_tokens

    def _decode_spec(self, last):
        """One [R, W] draft-propose + verify for every running row.

        Emits ``accepted + 1`` tokens per row (the accepted draft run
        plus the bonus greedy token after it) — between 1 and W per step,
        token-identical to vanilla decode by the greedy accept rule.
        """
        tr = self.tracer
        R, W, bs = self.pcfg.max_seqs, self.spec_window, self.pcfg.page_size
        with tr.span("pack"):
            window = np.zeros((R, W), np.int32)
            caps = np.zeros((R,), np.int32)
            for seq in self.sched.running.values():
                window[seq.slot, 0] = seq.last_token
                window[seq.slot, 1:] = self._propose(seq)
                remaining = seq.req.max_new - len(seq.emitted)
                caps[seq.slot] = max(0, min(
                    W - 1,
                    remaining - 1,                   # a+1 <= max_new budget
                    len(seq.pages) * bs - seq.length - 1))  # KV on real pages
        with tr.span("upload"):
            args = (jnp.asarray(window), jnp.asarray(self._active),
                    jnp.asarray(caps))
        with tr.span("dispatch"):
            g, a, self.cache = self._verify(self.params, args[0], self.cache,
                                            *args[1:])
        with tr.span("readback"):
            g, a = np.asarray(g, np.int32), np.asarray(a, np.int32)
        n_tokens = 0
        with tr.span("emit"):
            for seq in list(self.sched.running.values()):
                ar = int(a[seq.slot])
                toks = (list(window[seq.slot, 1:ar + 1])
                        + [int(g[seq.slot, ar])])
                if self.scfg.eos_id >= 0 and self.scfg.eos_id in toks:
                    toks = toks[: toks.index(self.scfg.eos_id) + 1]
                seq.emitted.extend(int(t) for t in toks)
                seq.last_token = seq.emitted[-1]
                seq.length += ar + 1        # matches the device-side bump
                n_tokens += len(toks)
                self._spec_accepted += ar
                self._spec_proposed += int(caps[seq.slot]) if W > 1 else 0
                if (len(seq.emitted) >= seq.req.max_new
                        or seq.emitted[-1] == self.scfg.eos_id
                        or seq.length + 1 > self.pcfg.tokens_per_seq):
                    self._step_finished.append(seq.rid)
                    self._finish(seq)
        return n_tokens

    def _step_mixed(self) -> dict:
        """One packed mixed-phase step: admit, COW-split, then ONE jitted
        call over [token_budget] lanes carrying prefill chunks and
        decode/spec windows together.

        The host packs segments (decode rows first — round-robin, with
        ``prefill_reserve`` lanes held back for chunks — then FCFS prefill
        chunks), runs ``self._mixed`` once, and walks the segment map:
        the tail lane of a prompt's last chunk yields its first token
        (TTFT), decode windows go through the same greedy accept rule as
        the batched verify step.

        Under the tracer, the ``step`` span's children are ``schedule``,
        ``cow``, ``plan``, ``pack``, ``set_tables``, ``upload``,
        ``dispatch``, ``readback``, ``emit`` and ``stats``, in that order.
        """
        tr = self.tracer
        self._step_finished: list[int] = []
        self._step_ttfts: list[float] = []
        self._spec_accepted = self._spec_proposed = 0
        plan = self._schedule()
        if plan.cow:
            # duplicate shared pages BEFORE any packed write lands on them
            with tr.span("cow"):
                self._apply_cow(plan.cow)
        with tr.span("plan"):
            segs = self.sched.plan_mixed(self.spec_window)
        with tr.span("pack"):
            lanes, caps, prefill_tokens, decode_lanes = self._pack(segs)
        n_tokens = 0
        if segs:
            n_tokens = self._run_mixed(segs, lanes, caps)
        elif self.sched.running:
            raise RuntimeError("mixed step planned no segments while rows "
                               "are running — scheduler liveness bug")
        with tr.span("stats"):
            self._step_idx += 1
            alloc = self.sched.alloc
            W = self.spec_window
            return {
                "step": self._step_idx,
                "admitted": [s.rid for s in plan.admitted],
                "preempted": plan.preempted,
                "finished": self._step_finished,
                "active": len(self.sched.running),
                "waiting": len(self.sched.waiting),
                "new_tokens": n_tokens,
                "decoded": decode_lanes > 0,
                "decode_rows": decode_lanes // W,
                "page_utilization": alloc.utilization,
                "cow_splits": len(plan.cow),
                "cache_hit_tokens": sum(s.cached_tokens
                                        for s in plan.admitted),
                "pages_allocated_total": alloc.pages_allocated,
                "pages_shared_total": alloc.pages_shared,
                "pages_window_evicted": self.sched.window_evictions,
                "spec_proposed": self._spec_proposed,
                "spec_accepted": self._spec_accepted,
                "rns_ops": self._rns_ops(0),
                # phase mix of this packed step + first-token latency
                "prefill_tokens": prefill_tokens,
                "decode_tokens": n_tokens,
                "ttft_ms": (1e3 * float(np.mean(self._step_ttfts))
                            if self._step_ttfts else 0.0),
            }

    def _schedule(self):
        """``sched.schedule()`` under the ``schedule`` span, then the
        ``admit`` and ``preempt`` request events of its plan."""
        with self.tracer.span("schedule"):
            plan = self.sched.schedule()
        for seq in plan.admitted:
            self.tracer.event(seq.rid, "admit")
        for rid in plan.preempted:
            self.tracer.event(rid, "preempt")
        return plan

    def _pack(self, segs):
        """Fill the [token_budget] lanes from the planned segments.

        Returns ((tokens, slots, positions, is_decode, is_valid), accept
        caps by slot, prefill tokens, decode lanes)."""
        N, W, bs = self.scfg.token_budget, self.spec_window, self.pcfg.page_size
        tok = np.zeros((N,), np.int32)
        sg = np.full((N,), -1, np.int32)
        ps = np.zeros((N,), np.int32)
        dc = np.zeros((N,), bool)
        vd = np.zeros((N,), bool)
        caps: dict[int, int] = {}
        off = 0
        prefill_tokens = decode_lanes = 0
        for s in segs:
            s.offset, n = off, s.n
            sg[off:off + n] = s.seq.slot
            ps[off:off + n] = s.positions
            vd[off:off + n] = True
            if s.kind == "decode":
                dc[off:off + n] = True
                tok[off] = s.seq.last_token
                if W > 1:
                    tok[off + 1:off + W] = self._propose(s.seq)
                remaining = s.seq.req.max_new - len(s.seq.emitted)
                caps[s.seq.slot] = max(0, min(
                    W - 1,
                    remaining - 1,
                    len(s.seq.pages) * bs - s.seq.length - 1))
                decode_lanes += n
            else:
                tok[off:off + n] = s.tokens
                prefill_tokens += n
            off += n
        return (tok, sg, ps, dc, vd), caps, prefill_tokens, decode_lanes

    def _run_mixed(self, segs, lanes, caps) -> int:
        """Upload the tables and lanes, run ``self._mixed`` once, read the
        per-lane argmaxes back and emit; returns the tokens emitted."""
        tr = self.tracer
        W = self.spec_window
        with tr.span("set_tables"):
            # tables go up every step: block tables shift under admission
            # / growth / COW, and the packed step reads positions directly
            # (cache lengths are advanced host-side only)
            bt, lengths, active, last = self.sched.tables()
            self.cache = kv.set_tables(self.cache, bt, lengths)
            self._active = active
            self._tables_dirty = False
        with tr.span("upload"):
            args = [jnp.asarray(a) for a in lanes]
        with tr.span("dispatch"):
            g, self.cache = self._mixed(self.params, *args, self.cache)
        with tr.span("readback"):
            g = np.asarray(g, np.int32)
        tok = lanes[0]
        n_tokens = 0
        with tr.span("emit"):
            now = time.perf_counter()
            for s in segs:
                seq = s.seq
                if s.kind == "chunk":
                    if s.last:
                        tok0 = int(g[s.offset + s.n - 1])
                        seq.emitted = [tok0]
                        seq.last_token = tok0
                        ttft = now - seq.req.submit_time
                        self.ttfts[seq.rid] = ttft
                        self._step_ttfts.append(ttft)
                        tr.event(seq.rid, "first_token", now)
                        n_tokens += 1
                    # full blocks become prefix-cache hits as they land,
                    # not only once the whole prompt is in
                    self.sched.register_chunks(seq)
                    if seq.emitted and (
                            len(seq.emitted) >= seq.req.max_new
                            or seq.emitted[-1] == self.scfg.eos_id):
                        self._step_finished.append(seq.rid)
                        self._finish(seq)
                else:
                    w = tok[s.offset:s.offset + s.n]
                    gr = g[s.offset:s.offset + s.n]
                    cap = caps[seq.slot]
                    ar = 0
                    while ar < cap and w[ar + 1] == gr[ar]:
                        ar += 1
                    toks = [int(t) for t in w[1:ar + 1]] + [int(gr[ar])]
                    if self.scfg.eos_id >= 0 and self.scfg.eos_id in toks:
                        toks = toks[: toks.index(self.scfg.eos_id) + 1]
                    seq.emitted.extend(toks)
                    seq.last_token = seq.emitted[-1]
                    seq.length += ar + 1
                    n_tokens += len(toks)
                    if W > 1:
                        self._spec_accepted += ar
                        self._spec_proposed += cap
                    if (len(seq.emitted) >= seq.req.max_new
                            or seq.emitted[-1] == self.scfg.eos_id
                            or seq.length + 1 > self.pcfg.tokens_per_seq):
                        self._step_finished.append(seq.rid)
                        self._finish(seq)
        return n_tokens

    def step(self) -> dict:
        """One scheduler step: admit/evict, prefill admits, COW-split
        shared pages, then decode (or draft+verify) every running row.

        Returns a stats dict: admitted/preempted/finished rids, tokens
        generated, page utilization, prefix-cache and speculative
        counters, and the structural ``rns_ops``.

        With ``chunked_prefill`` on, this dispatches to the packed
        mixed-phase step instead (same stats contract, plus chunked
        admission semantics).  Either way the step runs under the
        tracer's ``step`` span.
        """
        with self.tracer.span("step", self._step_idx + 1):
            if self.chunked:
                return self._step_mixed()
            return self._step_phased()

    def _step_phased(self) -> dict:
        """The phase-barrier step: whole-prompt prefill of each admission,
        then one [R, 1] decode (or [R, W] verify) over the running rows."""
        tr = self.tracer
        self._step_finished: list[int] = []
        self._step_ttfts: list[float] = []
        self._spec_accepted = self._spec_proposed = 0
        plan = self._schedule()
        if plan.admitted or plan.preempted or plan.grew or plan.cow:
            self._tables_dirty = True
        for seq in plan.admitted:
            self._do_prefill(seq)
        if plan.cow:
            # duplicate shared pages BEFORE any decode write lands on them
            with tr.span("cow"):
                self._apply_cow(plan.cow)
        # admission already produced one token per new row: those rows may
        # already be done (max_new=1 or eos on the first token)
        for seq in list(self.sched.running.values()):
            if seq.emitted and (
                    len(seq.emitted) >= seq.req.max_new
                    or seq.emitted[-1] == self.scfg.eos_id):
                self._step_finished.append(seq.rid)
                self._finish(seq)

        n_tokens = 0
        decoded = bool(self.sched.running)
        decode_rows = len(self.sched.running)
        if self.sched.running:
            with tr.span("set_tables"):
                bt, lengths, active, last = self.sched.tables()
                if self._tables_dirty or not np.array_equal(active,
                                                            self._active):
                    # topology changed: push fresh tables/lengths;
                    # otherwise the decode step's own active-masked length
                    # bump already matches the host counters and the
                    # upload is skipped
                    self.cache = kv.set_tables(self.cache, bt, lengths)
                    self._active = active
                    self._tables_dirty = False
            if self.scfg.spec_decode:
                n_tokens = self._decode_spec(last)
            else:
                n_tokens = self._decode_vanilla(last)
        with tr.span("stats"):
            self._step_idx += 1
            alloc = self.sched.alloc
            return {
                "step": self._step_idx,
                "admitted": [s.rid for s in plan.admitted],
                "preempted": plan.preempted,
                "finished": self._step_finished,
                "active": len(self.sched.running),
                "waiting": len(self.sched.waiting),
                "new_tokens": n_tokens,
                "decoded": decoded,
                "decode_rows": decode_rows,
                "page_utilization": alloc.utilization,
                # prefix-cache accounting (cumulative counters + this plan)
                "cow_splits": len(plan.cow),
                "cache_hit_tokens": sum(s.cached_tokens
                                        for s in plan.admitted),
                "pages_allocated_total": alloc.pages_allocated,
                "pages_shared_total": alloc.pages_shared,
                "pages_window_evicted": self.sched.window_evictions,
                # speculative accounting (this step)
                "spec_proposed": self._spec_proposed,
                "spec_accepted": self._spec_accepted,
                "rns_ops": self._rns_ops(len(plan.admitted)),
                # phase accounting (whole-prompt prefill counts padded
                # work at admission; decode tokens are this step's
                # emissions)
                "prefill_tokens": sum(len(s.req.tokens)
                                      for s in plan.admitted),
                "decode_tokens": n_tokens,
                "ttft_ms": (1e3 * float(np.mean(self._step_ttfts))
                            if self._step_ttfts else 0.0),
            }

    def run(self, prompts=None, max_new: int | None = None):
        """Serve until drained.  Returns (results {rid: tokens}, stats).

        ``prompts``: optional list of 1-D prompt arrays to submit first.
        Delivered results are *drained* from the engine (a long-lived
        engine does not accumulate them); latency percentiles cover
        submit -> finish, queue wait included.  Streaming users driving
        ``submit()``/``step()`` directly read — and should pop —
        ``engine.results`` / ``engine.latencies`` themselves.
        """
        rids = [self.submit(p, max_new) for p in (prompts or [])]
        t0 = time.perf_counter()
        steps = []
        while self.sched.has_work:
            steps.append(self.step())
        dt = time.perf_counter() - t0
        done = rids if rids else list(self.results)
        out = {r: self.results.pop(r) for r in done if r in self.results}
        lat = [self.latencies.pop(r) for r in done if r in self.latencies]
        ttft = [self.ttfts.pop(r) for r in done if r in self.ttfts]
        total = sum(len(v) for v in out.values())
        decode_rows = sum(s["decode_rows"] for s in steps)
        new_in_decode = sum(s["new_tokens"] for s in steps)
        proposed = sum(s["spec_proposed"] for s in steps)
        accepted = sum(s["spec_accepted"] for s in steps)
        stats = {
            "n_requests": len(done),
            "n_steps": len(steps),
            "total_new_tokens": total,
            "wall_s": dt,
            "tokens_per_s": total / dt if dt > 0 else 0.0,
            "latency_p50_s": float(np.percentile(lat, 50)) if lat else 0.0,
            "latency_p99_s": float(np.percentile(lat, 99)) if lat else 0.0,
            "ttft_p50_s": float(np.percentile(ttft, 50)) if ttft else 0.0,
            "ttft_p95_s": float(np.percentile(ttft, 95)) if ttft else 0.0,
            "mean_page_utilization": float(
                np.mean([s["page_utilization"] for s in steps])) if steps
            else 0.0,
            "n_preemptions": sum(len(s["preempted"]) for s in steps),
            # speculative decoding: mean decoded tokens per ROW per decode
            # step (> 1 iff drafts are being accepted) and the acceptance
            # rate over eligible (cap-respecting) drafts
            "tokens_per_step": (new_in_decode / decode_rows
                                if decode_rows else 0.0),
            "acceptance_rate": accepted / proposed if proposed else 0.0,
            # prefix caching: cumulative allocator/COW traffic
            "cache_hit_tokens": sum(s["cache_hit_tokens"] for s in steps),
            "cow_splits": sum(s["cow_splits"] for s in steps),
            "pages_allocated": self.sched.alloc.pages_allocated,
            "pages_shared": self.sched.alloc.pages_shared,
            # sliding window: cumulative pages recycled by eviction
            "pages_window_evicted": self.sched.window_evictions,
            "steps": steps,
        }
        return out, stats
