"""Model sizes from a configuration file, and weights drawn from a seed.

The weights are made on the device in one jitted call, in bfloat16, the type
they are served and trained in.  The tree has the layout the system under
test takes (`params=` of its engine and trainer); the reference in
`bench/reference.py` draws the same tree again from the same seed after the
system's state is freed, so it takes nothing the system made.
"""
from __future__ import annotations

import json
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Dims:
    name: str
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    eps: float
    rope_theta: float
    tied: bool

    @property
    def nq(self) -> int:
        return self.heads * self.head_dim

    @property
    def nkv(self) -> int:
        return self.kv_heads * self.head_dim


def load_config(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def dims_of(cfg: dict) -> Dims:
    heads = int(cfg["num_attention_heads"])
    return Dims(name=cfg["name"], layers=int(cfg["num_hidden_layers"]),
                d=int(cfg["hidden_size"]), heads=heads,
                kv_heads=int(cfg["num_key_value_heads"]),
                head_dim=int(cfg.get("head_dim") or
                             cfg["hidden_size"] // heads),
                ff=int(cfg["intermediate_size"]),
                vocab=int(cfg["vocab_size"]),
                eps=float(cfg["rms_norm_eps"]),
                rope_theta=float(cfg["rope_theta"]),
                tied=bool(cfg["tie_word_embeddings"]))


def param_count(dm: Dims) -> int:
    per_layer = (dm.d * dm.nq * 2 + dm.d * dm.nkv * 2 + dm.nq + 2 * dm.nkv
                 + 3 * dm.d * dm.ff + 2 * dm.d)
    return (dm.layers * per_layer + dm.vocab * dm.d * (1 if dm.tied else 2)
            + dm.d)


def seed32(seed: int, stream: int = 0) -> int:
    """A 32-bit key for `stream` from any whole-number seed."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])


def _leaf_specs(dm: Dims) -> dict:
    """name -> (shape, kind, std); kind is 'normal', 'bias' or 'norm'."""
    L, d, f = dm.layers, dm.d, dm.ff
    out_std = 1.0 / np.sqrt(2.0 * L)
    specs = {
        "blocks/attn/wq": ((L, d, dm.nq), "normal", d ** -0.5),
        "blocks/attn/wk": ((L, d, dm.nkv), "normal", d ** -0.5),
        "blocks/attn/wv": ((L, d, dm.nkv), "normal", d ** -0.5),
        "blocks/attn/wo": ((L, dm.nq, d), "normal", dm.nq ** -0.5 * out_std),
        "blocks/attn/bq": ((L, dm.nq), "bias", 0.1),
        "blocks/attn/bk": ((L, dm.nkv), "bias", 0.1),
        "blocks/attn/bv": ((L, dm.nkv), "bias", 0.1),
        "blocks/ffn/gate": ((L, d, f), "normal", d ** -0.5),
        "blocks/ffn/up": ((L, d, f), "normal", d ** -0.5),
        "blocks/ffn/down": ((L, f, d), "normal", f ** -0.5 * out_std),
        "blocks/ln1": ((L, d), "norm", 0.1),
        "blocks/ln2": ((L, d), "norm", 0.1),
        "embed": ((dm.vocab, d), "normal", 0.02),
        "ln_f": ((d,), "norm", 0.1),
    }
    if not dm.tied:
        specs["head"] = ((d, dm.vocab), "normal", d ** -0.5)
    return specs


def _draw(key, shape, kind, std):
    x = jax.random.normal(key, shape, jnp.bfloat16) * jnp.bfloat16(std)
    if kind == "norm":
        x = x + jnp.bfloat16(1.0)
    return x


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for name, v in flat.items():
        node = tree
        *parents, leaf = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def make_params(dm: Dims, seed: int, sharding=None) -> dict:
    """The whole bfloat16 tree, drawn on the device in one jitted call.
    `sharding` (one for every leaf) places it; default: the first device."""
    specs = _leaf_specs(dm)
    names = sorted(specs)

    def draw(key):
        keys = jax.random.split(key, len(names))
        return _nest({n: _draw(k, *specs[n]) for n, k in zip(names, keys)})

    out_sh = None
    if sharding is not None:
        out_sh = jax.tree.map(lambda _: sharding, _nest(
            {n: 0 for n in names}))
    key = jax.random.PRNGKey(seed32(seed, 1))
    return jax.jit(draw, out_shardings=out_sh)(key)


def param_bytes(dm: Dims) -> int:
    return 2 * param_count(dm)
