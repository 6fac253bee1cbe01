"""The main path compiled for a TPU v5e by the installed TPU compiler.

Interpret-mode tests cannot see what Mosaic refuses (a block not aligned
to the (8, 128) tiling, an s32 x s32 MXU matmul).  These tests compile
the 7 kernel launches of the residue datapath at smollm-135m's published
MLP widths for one chip of a described ``v5e:2x2`` topology, plus the
packed serve step at full width, and check that the kernels are in the
compiled program.  Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module fixture, never at import, so
that only the pytest worker running this file loads the TPU library.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.core.moduli import get_profile
from repro.core.rns_matmul import RnsDotConfig

PROFILE = "rns9"
ROWS = 128          # one M tile of activation rows
BITS = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compile_for_v5e(one_chip, no_compile_cache):
    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile()

    return compile_


def _launches():
    """name -> (fn over operands, operand (shape, dtype) list)."""
    from repro.kernels.rns_convert.ops import rns_convert
    from repro.kernels.rns_fused import ops as fused
    from repro.kernels.rns_matmul.ops import rns_matmul
    from repro.kernels.rns_normalize.ops import rns_normalize

    cfg = get_config("smollm-135m")
    K = get_profile(PROFILE).n_digits
    M, D, N = ROWS, cfg.d_model, cfg.d_ff
    i8, i32, f32 = jnp.int8, jnp.int32, jnp.float32
    a, b = ((K, M, D), i8), ((K, D, N), i8)
    x, rows = ((M, D), f32), ((M, 1), f32)
    y = ((M, N), f32)
    return {
        "rns_matmul": (
            lambda a, b: rns_matmul(PROFILE, a, b, interpret=False), [a, b]),
        "rns_fused_encode_matmul": (
            lambda x, s, b: fused.rns_fused_encode_matmul(
                PROFILE, x, s, b, bits=BITS, interpret=False),
            [x, rows, b]),
        "rns_fused_matmul_normalize": (
            lambda a, b: fused.rns_fused_matmul_normalize(
                PROFILE, a, b, interpret=False), [a, b]),
        "rns_fused_dot": (
            lambda x, s, b: fused.rns_fused_dot(
                PROFILE, x, s, b, bits=BITS, interpret=False),
            [x, rows, b]),
        "rns_normalize": (
            lambda r: rns_normalize(PROFILE, r, interpret=False),
            [((K, M, N), i32)]),
        "rns_convert_scalar_scale": (
            lambda y, s: rns_convert(PROFILE, y, s, bits=BITS,
                                     interpret=False),
            [y, ((), f32)]),
        "rns_convert_row_scales": (
            lambda y, s: rns_convert(PROFILE, y, s, bits=BITS,
                                     interpret=False),
            [y, rows]),
    }


@pytest.mark.parametrize("name", [
    "rns_matmul", "rns_fused_encode_matmul", "rns_fused_matmul_normalize",
    "rns_fused_dot", "rns_normalize", "rns_convert_scalar_scale",
    "rns_convert_row_scales"])
def test_kernel_compiles_for_v5e(compile_for_v5e, name):
    fn, shapes = _launches()[name]
    compiled = compile_for_v5e(fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


def test_packed_serve_step_compiles_for_v5e(compile_for_v5e, monkeypatch):
    """The jitted mixed step of a resident, fused-kernel RNS engine at
    smollm-135m's published widths, from shapes only."""
    from repro.core import dispatch
    from repro.models import model as M
    from repro.models.resident import encode_resident
    from repro.serve import kv_cache as kv

    # the platform here is the CPU; compile the kernels, not the interpreter
    monkeypatch.setattr(dispatch, "default_interpret", lambda: False)
    cfg = dataclasses.replace(
        get_config("smollm-135m"),
        rns=RnsDotConfig(PROFILE, qx=BITS, qw=BITS, backend="pallas_fused"),
        rns_targets="mlp")
    params = jax.eval_shape(lambda: encode_resident(
        M.init_model(jax.random.PRNGKey(0), cfg)[0], cfg))
    pcfg = kv.PagedCacheConfig(page_size=16, n_pages=1 + 8 * 64, max_seqs=8,
                               max_blocks=64)
    cache = jax.eval_shape(
        lambda: kv.make_paged_cache(cfg, pcfg, dtype=jnp.float32))
    leaves, tree = jax.tree.flatten((params, cache))
    lanes = 64

    def step(tokens, seg, pos, dec, valid, *flat):
        params, cache = jax.tree.unflatten(tree, flat)
        logits, cache = M.mixed_step(params, cfg, tokens, seg, pos, dec,
                                     valid, cache)
        return jnp.argmax(logits, axis=-1), cache

    compiled = compile_for_v5e(
        step, ((lanes,), jnp.int32), ((lanes,), jnp.int32),
        ((lanes,), jnp.int32), ((lanes,), jnp.bool_), ((lanes,), jnp.bool_),
        *[(leaf.shape, leaf.dtype) for leaf in leaves])
    assert "tpu_custom_call" in compiled.as_text()
