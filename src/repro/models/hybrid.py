"""Zamba2-style hybrid: Mamba2 backbone with one weight-shared attention+MLP
block applied every `attn_every` mamba blocks.

The shared block's params are NOT stacked (one copy); inside the layer scan a
lax.cond applies it at interleave sites.  Its KV caches ARE per-site (the
block re-reads different depths), stacked on a leading sites dim.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models.param import PD
from repro.sharding import TP_AXIS, constrain

Gather = Optional[Callable]


class HybridLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dims = L.AttnDims(
            num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim,
            rope_theta=cfg.rope_theta,
            window=None,
        )
        self.n_sites = cfg.num_layers // cfg.attn_every

    def param_defs(self) -> dict:
        c = self.cfg
        d, f = c.d_model, c.d_ff
        Dh = c.resolved_head_dim
        nq, nkv = c.num_heads * Dh, c.num_kv_heads * Dh
        shared = {
            "attn": {
                "wq": PD((d, nq), ("d_model", "heads")),
                "wk": PD((d, nkv), ("d_model", "kv_heads")),
                "wv": PD((d, nkv), ("d_model", "kv_heads")),
                "wo": PD((nq, d), ("heads", "d_model"), scale=nq ** -0.5),
            },
            "ffn": {
                "gate": PD((d, f), ("d_model", "ff")),
                "up": PD((d, f), ("d_model", "ff")),
                "down": PD((f, d), ("ff", "d_model"), scale=f ** -0.5),
            },
            "ln1": PD((d,), ("d_model",), init="ones"),
            "ln2": PD((d,), ("d_model",), init="ones"),
        }
        return {
            "blocks": M.mamba_block_defs(c, c.num_layers),
            "shared": shared,
            "embed": PD((c.vocab_size, d), ("vocab", "d_model"), scale=0.02),
            "head": PD((d, c.vocab_size), ("d_model", "vocab")),
            "ln_f": PD((d,), ("d_model",), init="ones"),
        }

    def _shared_apply(self, sp: dict, x: jax.Array, positions) -> jax.Array:
        c = self.cfg
        h = L.rms_norm(x, sp["ln1"], c.norm_eps)
        x = x + L.attention(sp["attn"], h, self.dims, positions=positions)
        h = L.rms_norm(x, sp["ln2"], c.norm_eps)
        return x + L.swiglu(sp["ffn"], h)

    def hidden_states(self, params, batch, *, gather: Gather = None):
        c = self.cfg
        gather = gather or (lambda p: p)
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
        S = x.shape[1]
        positions = jnp.arange(S)
        sp = params["shared"]

        def body(lp, x, i):
            x = M.mamba_forward(gather(lp), x, c)
            x = jax.lax.cond(
                (i % c.attn_every) == (c.attn_every - 1),
                lambda xx: self._shared_apply(sp, xx, positions),
                lambda xx: xx,
                x)
            return x

        if c.remat:
            body = jax.checkpoint(body)

        def step(carry, lp):
            x, i = carry
            return (body(lp, x, i), i + 1), None

        (x, _), _ = jax.lax.scan(step, (x, jnp.int32(0)), params["blocks"])
        return L.rms_norm(x, params["ln_f"], c.norm_eps), jnp.float32(0.0), 0

    def loss(self, params, batch, *, gather: Gather = None):
        tokens = batch["tokens"]
        x, _, _ = self.hidden_states(params, {**batch, "tokens": tokens[:, :-1]},
                                     gather=gather)
        sum_loss, count = L.chunked_ce_loss(x, params["head"], tokens[:, 1:])
        loss = sum_loss / jnp.maximum(count, 1.0)
        return loss, {"ce_loss": loss, "aux_loss": jnp.float32(0.0), "tokens": count}

    def logits(self, params, batch, *, gather: Gather = None):
        x, _, _ = self.hidden_states(params, batch, gather=gather)
        return constrain((x @ params["head"]).astype(jnp.float32),
                         None, None, TP_AXIS)

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------

    def cache_defs(self, batch_size: int, max_len: int) -> dict:
        c = self.cfg
        Dh = c.resolved_head_dim
        defs = M.mamba_state_defs(c, c.num_layers, batch_size)
        kv = ("sites", "batch", "seq", "kv_heads", None)
        defs["shared_k"] = PD((self.n_sites, batch_size, max_len, c.num_kv_heads, Dh),
                              kv, init="zeros")
        defs["shared_v"] = PD((self.n_sites, batch_size, max_len, c.num_kv_heads, Dh),
                              kv, init="zeros")
        return defs

    def decode_step(self, params, cache, pos, tokens, *, gather: Gather = None):
        c = self.cfg
        gather = gather or (lambda p: p)
        x = jnp.take(params["embed"], tokens, axis=0)
        sp = params["shared"]
        n_sites = self.n_sites

        def mamba_step(x, inp):
            lp, ssm, conv = inp
            x, new = M.mamba_decode(gather(lp), {"ssm": ssm, "conv": conv}, x, c)
            return x, (new["ssm"], new["conv"])

        # interleave: run groups of attn_every mamba layers, then a shared
        # attention site.  Python loop over sites (static, small).
        ssm_out, conv_out, k_out, v_out = [], [], [], []
        per = c.attn_every
        for site in range(n_sites):
            sl = slice(site * per, (site + 1) * per)
            seg = jax.tree.map(lambda a: a[sl], params["blocks"])
            x, (ssm_n, conv_n) = jax.lax.scan(
                mamba_step, x, (seg, cache["ssm"][sl], cache["conv"][sl]))
            ssm_out.append(ssm_n)
            conv_out.append(conv_n)
            h = L.rms_norm(x, sp["ln1"], c.norm_eps)
            a, k_row, v_row = L.decode_attention(
                sp["attn"], h, self.dims,
                k_cache=cache["shared_k"][site], v_cache=cache["shared_v"][site],
                pos=pos, ring=False)
            x = x + a
            h = L.rms_norm(x, sp["ln2"], c.norm_eps)
            x = x + L.swiglu(sp["ffn"], h)
            k_out.append(k_row)
            v_out.append(v_row)
        # trailing mamba layers (if num_layers % attn_every)
        rem = c.num_layers - n_sites * per
        if rem:
            sl = slice(n_sites * per, c.num_layers)
            seg = jax.tree.map(lambda a: a[sl], params["blocks"])
            x, (ssm_n, conv_n) = jax.lax.scan(
                mamba_step, x, (seg, cache["ssm"][sl], cache["conv"][sl]))
            ssm_out.append(ssm_n)
            conv_out.append(conv_n)
        x = L.rms_norm(x, params["ln_f"], c.norm_eps)
        logits = constrain((x @ params["head"]).astype(jnp.float32),
                           None, None, TP_AXIS)
        new_cache = {
            "ssm": jnp.concatenate(ssm_out, axis=0),
            "conv": jnp.concatenate(conv_out, axis=0),
            "shared_k": L.put_kv_rows(cache["shared_k"], jnp.stack(k_out), pos),
            "shared_v": L.put_kv_rows(cache["shared_v"], jnp.stack(v_out), pos),
        }
        return logits, new_cache

    def prefill(self, params, batch, *, gather: Gather = None):
        """Full-prompt pass producing mamba states + shared-site KV caches."""
        c = self.cfg
        gather = gather or (lambda p: p)
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0)
        positions = jnp.arange(S)
        sp = params["shared"]
        per = c.attn_every

        def seg_step(x, lp):
            lp = gather(lp)
            y = M.mamba_forward(lp, x, c)
            st = M._final_state(lp, x, c)
            return y, (st["ssm"], st["conv"])

        ssm_out, conv_out, k_out, v_out = [], [], [], []
        n_full = self.n_sites
        for site in range(n_full):
            sl = slice(site * per, (site + 1) * per)
            seg = jax.tree.map(lambda a: a[sl], params["blocks"])
            x, (ssm_n, conv_n) = jax.lax.scan(seg_step, x, seg)
            ssm_out.append(ssm_n)
            conv_out.append(conv_n)
            h = L.rms_norm(x, sp["ln1"], c.norm_eps)
            q, k, v = L._project_qkv(sp["attn"], h, self.dims, positions)
            from repro.kernels import ops
            o = ops.flash_attention(q, k, v, causal=True)
            x = x + o.reshape(B, S, -1) @ sp["attn"]["wo"]
            h = L.rms_norm(x, sp["ln2"], c.norm_eps)
            x = x + L.swiglu(sp["ffn"], h)
            k_out.append(k)
            v_out.append(v)
        rem = c.num_layers - n_full * per
        if rem:
            sl = slice(n_full * per, c.num_layers)
            seg = jax.tree.map(lambda a: a[sl], params["blocks"])
            x, (ssm_n, conv_n) = jax.lax.scan(seg_step, x, seg)
            ssm_out.append(ssm_n)
            conv_out.append(conv_n)
        x = L.rms_norm(x, params["ln_f"], c.norm_eps)
        logits = constrain((x[:, -1:] @ params["head"]).astype(jnp.float32),
                           None, None, TP_AXIS)
        cache = {
            "ssm": jnp.concatenate(ssm_out, axis=0),
            "conv": jnp.concatenate(conv_out, axis=0),
            "shared_k": jnp.stack(k_out, axis=0),
            "shared_v": jnp.stack(v_out, axis=0),
        }
        return logits, cache
