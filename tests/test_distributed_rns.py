"""Residue-channel (digit-axis) sharding over a multi-device mesh.

The paper's digit-independence claim, executed as a distribution
strategy on 8 virtual CPU devices (a subprocess, because the suite's
own jax is pinned to 1 device):

  * a digit-sharded 3-linear chain decodes BIT-IDENTICALLY to the
    single-device reference;
  * the compiled residue segment (convert + matmuls + deferred
    elementwise mul) contains ZERO cross-device collectives — digits
    never exchange carries; the full chain's HLO contains the one
    normalize-time digit gather (which also proves the sharded trace
    actually engaged: jax's trace cache is keyed on function identity,
    so the two paths use distinct function defs);
  * DP x digit composition: `make_dp_train_step` on a (2, 4) mesh
    produces losses matching the single-device step to float tolerance;
  * the continuous serving engine with `ServeConfig.mesh` set decodes
    token-identically to the unsharded engine.

Pure-layout unit checks (DigitSharding rules) run in-process.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestDigitShardingRules:
    def _mesh(self, shape=(1, 8), axes=("data", "model")):
        from jax.sharding import AbstractMesh

        return AbstractMesh(shape, axes)

    def test_shards_requires_divisibility(self):
        from repro.distributed.sharding import DigitSharding

        ds = DigitSharding(self._mesh((1, 8)))
        assert ds.n_shards == 8
        assert ds.shards(16) and ds.shards(8)
        assert not ds.shards(9)        # rns9 does not divide 8 devices
        assert ds.auto_axes() == {"data"}

    def test_digit_spec_shape(self):
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import DigitSharding

        ds = DigitSharding(self._mesh((2, 4)))
        assert ds.digit_spec(3) == P("model", None, None)

    def test_context_install_and_noop(self):
        from repro.distributed.sharding import (
            digit_sharding,
            use_digit_sharding,
        )

        assert digit_sharding() is None
        with use_digit_sharding(None):            # no-op form
            assert digit_sharding() is None
        mesh = self._mesh((1, 4))
        with use_digit_sharding(mesh) as ds:
            assert digit_sharding() is ds and ds.axis == "model"
        assert digit_sharding() is None

    def test_rt_device_put_places_digit_layout(self):
        # concrete 1x1 mesh (the suite's jax is pinned to 1 CPU device):
        # placement is a no-op partition but the layout contract holds
        import jax.numpy as jnp
        import numpy as np

        from jax.sharding import PartitionSpec as P

        from repro.core.tensor import (
            rt_device_put,
            rt_digit_sharding,
            rt_encode,
        )
        from repro.distributed.sharding import use_digit_sharding
        from repro.launch.mesh import make_digit_mesh

        x = jnp.asarray(np.arange(8, dtype=np.float32).reshape(2, 4))
        rt = rt_encode(x, "rns16", bits=8)
        assert rt_digit_sharding(rt) is None          # no context: no-op
        assert rt_device_put(rt) is rt
        with use_digit_sharding(make_digit_mesh()):
            sh = rt_digit_sharding(rt)
            assert sh is not None
            assert sh.spec == P("model", None, None)
            placed = rt_device_put(rt)
            assert placed.digits.sharding == sh
            assert np.array_equal(np.asarray(placed.digits),
                                  np.asarray(rt.digits))


SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import dataclasses, json, warnings
warnings.filterwarnings("ignore")
import numpy as np
import jax, jax.numpy as jnp

from repro.launch.mesh import make_digit_mesh
from repro.distributed.sharding import use_digit_sharding
from repro.core.tensor import rt_encode, rt_matmul, rt_mul, rt_decode

out = {"n_devices": jax.device_count()}
mesh = make_digit_mesh(8)                 # (1, 8) data x model
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
ws = [jnp.asarray(rng.standard_normal((64, 64)) / 8, jnp.float32)
      for _ in range(3)]

# NOTE distinct function defs for the sharded/unsharded variants: jax's
# trace cache is keyed on function identity and would otherwise reuse
# the first trace, silently ignoring the digit context.
def chain_ref(x, ws):
    ht = rt_encode(x, "rns16", bits=8)
    for w in ws:
        ht = rt_matmul(ht, rt_encode(w, "rns16", bits=8))
    return rt_decode(ht)

def chain_sharded(x, ws):
    ht = rt_encode(x, "rns16", bits=8)
    for w in ws:
        ht = rt_matmul(ht, rt_encode(w, "rns16", bits=8))
    return rt_decode(ht)

def residue_segment(x, ws):
    # encode + matmul chain + deferred elementwise mul; residues out, NO
    # normalize -> its HLO must be collective-free
    ht = rt_encode(x, "rns16", bits=8)
    for w in ws:
        ht = rt_matmul(ht, rt_encode(w, "rns16", bits=8))
    ht = rt_mul(ht, rt_encode(x, "rns16", bits=8))
    return ht.digits

COLL = ("all-reduce", "all-to-all", "collective-permute", "all-gather",
        "reduce-scatter")
def n_coll(hlo):
    return sum(1 for l in hlo.splitlines()
               if "=" in l and any(c in l for c in COLL))

y_ref = jax.jit(chain_ref)(x, ws)
with use_digit_sharding(mesh):
    y_sh = jax.jit(chain_sharded)(x, ws)
    seg_hlo = jax.jit(residue_segment).lower(x, ws).compile().as_text()
    full_hlo = jax.jit(chain_sharded).lower(x, ws).compile().as_text()
out["chain_bitexact"] = bool(jnp.all(y_ref == y_sh))
out["residue_segment_collectives"] = n_coll(seg_hlo)
out["full_chain_collectives"] = n_coll(full_hlo)
out["digits_sharded"] = "s32[2,4,64]" in seg_hlo  # 16 digits / 8 devices

# ---- DP x digit train step -----------------------------------------------
from repro.configs.base import get_config
from repro.core.rns_matmul import RnsDotConfig
from repro.models import model as M
from repro.optim.adamw import AdamWConfig
from repro.train.train_step import (
    init_train_state, make_train_step, make_dp_train_step)

cfg = dataclasses.replace(get_config("smollm-135m", smoke=True),
                          rns=RnsDotConfig(profile="rns8", qx=8, qw=8),
                          rns_targets="mlp")
mesh24 = make_digit_mesh(4, n_data=2)
opt = AdamWConfig(lr=1e-3)
state_a, _ = init_train_state(jax.random.PRNGKey(0), cfg)
state_b = jax.tree.map(jnp.copy, state_a)
batch = {"tokens": jnp.asarray(rng.integers(1, cfg.vocab, (4, 16)),
                               jnp.int32)}
step_1 = jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))
step_dp = make_dp_train_step(cfg, opt, mesh24)
l1, ldp = [], []
for _ in range(2):
    state_a, m1 = step_1(state_a, batch)
    state_b, m2 = step_dp(state_b, batch)
    l1.append(float(m1["loss"])); ldp.append(float(m2["loss"]))
out["single_losses"], out["dp_losses"] = l1, ldp
out["dp_loss_close"] = bool(np.allclose(l1, ldp, rtol=1e-5, atol=1e-5))

# ---- sharded continuous serving ------------------------------------------
from repro.serve.engine import ContinuousEngine, ServeConfig

params, _ = M.init_model(jax.random.PRNGKey(1), cfg)
prompts = [rng.integers(1, cfg.vocab, (L,)).astype(np.int32)
           for L in (7, 20)]
res_u, _ = ContinuousEngine(params, cfg, ServeConfig(
    max_cache=48, max_new_tokens=5, page_size=16, max_seqs=2)).run(prompts)
res_s, stats = ContinuousEngine(params, cfg, ServeConfig(
    max_cache=48, max_new_tokens=5, page_size=16, max_seqs=2,
    mesh=mesh)).run(prompts)
out["serve_sharded_identical"] = all(
    res_u[i].tolist() == res_s[i].tolist() for i in range(len(prompts)))
out["serve_tokens"] = {str(i): res_s[i].tolist() for i in res_s}
print("RESULT:" + json.dumps(out))
"""


@pytest.mark.slow
def test_digit_sharded_execution_8_devices():
    """End-to-end: exactness, collective-free residues, DP x digit, serve."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SUBPROC], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    assert out["n_devices"] == 8
    # digit-sharded chain decodes bit-identically to single-device
    assert out["chain_bitexact"]
    # the residue segment's HLO has ZERO cross-device collectives ...
    assert out["residue_segment_collectives"] == 0
    assert out["digits_sharded"]        # 2-of-16 digit planes per device
    # ... and the full chain has (only) the normalize-time digit gather,
    # which also proves the sharded trace engaged at all
    assert out["full_chain_collectives"] > 0
    # DP-sharded train_step losses match single-device to float tolerance
    assert out["dp_loss_close"], (out["single_losses"], out["dp_losses"])
    # sharded continuous decode is token-identical to unsharded
    assert out["serve_sharded_identical"], out["serve_tokens"]
