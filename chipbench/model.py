"""A configuration file's model: its sizes, its weights from the seed, and
the program's config and serve settings built from them.

The file's ``model`` block uses the source's own key names (the
Hugging Face ``config.json`` names).  Weights are the benchmark's own:
one jitted call makes every leaf on the device from the seed, in the
layout the program's parameter tree has, and the plain reference
(``reference.py``) reads the same arrays.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    rope_theta: float
    eps: float
    tied: bool

    @classmethod
    def of(cls, cfgfile: dict) -> "Dims":
        m = cfgfile["model"]
        return cls(layers=int(m["num_hidden_layers"]),
                   d=int(m["hidden_size"]),
                   heads=int(m["num_attention_heads"]),
                   kv_heads=int(m["num_key_value_heads"]),
                   head_dim=int(m["head_dim"]),
                   ff=int(m["intermediate_size"]),
                   vocab=int(m["vocab_size"]),
                   rope_theta=float(m["rope_theta"]),
                   eps=float(m["rms_norm_eps"]),
                   tied=bool(m["tie_word_embeddings"]))


def seed_key(seed: int):
    """A threefry key from any whole-number seed (also past 32 bits)."""
    import jax

    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(state, impl="threefry2x32")


def weight_shapes(dm: Dims) -> dict:
    """Leaf shapes of the parameter tree, stacked over layers."""
    L, d, f = dm.layers, dm.d, dm.ff
    q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    tree = {
        "embed": {"table": (dm.vocab, d)},
        "blocks": {"l0": {
            "ln1": {"scale": (L, d)},
            "attn": {"wq": {"w": (L, d, q)}, "wk": {"w": (L, d, kv)},
                     "wv": {"w": (L, d, kv)}, "wo": {"w": (L, q, d)}},
            "ln2": {"scale": (L, d)},
            "mlp": {"wi": {"w": (L, d, f)}, "wg": {"w": (L, d, f)},
                    "wo": {"w": (L, f, d)}},
        }},
        "final_norm": {"scale": (d,)},
    }
    if not dm.tied:
        tree["lm_head"] = {"w": (d, dm.vocab)}
    return tree


def make_weights(dm: Dims, seed: int):
    """Every weight on the device from ``seed``, in one jitted call:
    linears N(0, 1/fan_in), the embedding N(0, 0.02^2), norm scales
    1 + N(0, 0.1^2), all float32 as served."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(dm), is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        out = []
        for i, (path, shape) in enumerate(flat):
            name = jax.tree_util.keystr(path)
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if "scale" in name:
                out.append(1.0 + 0.1 * z)
            elif "table" in name:
                out.append(0.02 * z)
            else:
                out.append(z / np.sqrt(shape[-2]))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)(seed_key(seed))


def program_config(cfgfile: dict):
    """(ModelConfig, ServeConfig) of the program, as the file states."""
    from repro.configs.base import get_config
    from repro.core.rns_matmul import RnsDotConfig
    from repro.serve.engine import ServeConfig

    dm = Dims.of(cfgfile)
    num = cfgfile["numerics"]
    base = get_config(cfgfile["registry"])
    if (base.norm, base.act, base.pos_emb, base.gated_mlp, base.qkv_bias,
            base.emb_scale) != ("rmsnorm", "silu", "rope", True, False,
                                False) or cfgfile["model"]["hidden_act"] \
            != "silu":
        raise ValueError(f"{cfgfile['name']}: the reference computes a "
                         "rope/RMSNorm/gated-SiLU decoder only")
    cfg = dataclasses.replace(
        base, n_layers=dm.layers, d_model=dm.d,
        n_heads=dm.heads, n_kv_heads=dm.kv_heads, d_head=dm.head_dim,
        d_ff=dm.ff, vocab=dm.vocab, rope_theta=dm.rope_theta,
        tie_embeddings=dm.tied, layer_types=("attn",) * dm.layers,
        mlp_types=("dense",) * dm.layers,
        rns=RnsDotConfig(num["profile"], qx=int(num["qx"]),
                         qw=int(num["qw"])),
        rns_targets=num["rns_targets"])
    scfg = ServeConfig(rns_backend=num["rns_backend"],
                       resident_weights=bool(num["resident_weights"]),
                       audit=False, **cfgfile["serve"])
    return cfg, scfg
