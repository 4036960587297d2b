"""Decoder-only (and encoder-decoder) transformer LM.

Covers the dense, moe, vlm (stub patch-embedding inputs) and audio (stub
frame-embedding inputs, encoder-decoder) families.  Layers are scanned with
stacked params (compile time independent of depth); an optional `gather`
callable is applied to each layer's params inside the scan body — the
ZeRO-3/FSDP hook: the train step passes an all-gather-over-"data", and
because it sits inside jax.checkpoint, backward re-gathers and autodiff
turns the gather into a reduce-scatter of gradients.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models.param import PD
from repro.sharding import TP_AXIS, constrain

Gather = Optional[Callable]


def _identity_gather(p):
    return p


class Transformer:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dims = L.AttnDims(
            num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim,
            rope_theta=cfg.rope_theta,
            window=cfg.sliding_window,
        )

    # ------------------------------------------------------------------
    # parameter definitions
    # ------------------------------------------------------------------

    def _attn_defs(self, n_layers: int) -> dict:
        c = self.cfg
        Dh = c.resolved_head_dim
        nq, nkv = c.num_heads * Dh, c.num_kv_heads * Dh
        d = c.d_model
        defs = {
            "wq": PD((n_layers, d, nq), ("layers", "d_model", "heads")),
            "wk": PD((n_layers, d, nkv), ("layers", "d_model", "kv_heads")),
            "wv": PD((n_layers, d, nkv), ("layers", "d_model", "kv_heads")),
            "wo": PD((n_layers, nq, d), ("layers", "heads", "d_model"),
                     scale=(nq ** -0.5) / (2 * c.num_layers) ** 0.5),
        }
        if c.qkv_bias:
            defs["bq"] = PD((n_layers, nq), ("layers", "heads"), init="zeros")
            defs["bk"] = PD((n_layers, nkv), ("layers", "kv_heads"), init="zeros")
            defs["bv"] = PD((n_layers, nkv), ("layers", "kv_heads"), init="zeros")
        return defs

    def _ffn_defs(self, n_layers: int) -> dict:
        c = self.cfg
        d, f = c.d_model, c.d_ff
        if c.moe is not None:
            E = c.moe.num_experts
            return {
                "router": PD((n_layers, d, E), ("layers", "d_model", None)),
                "gate": PD((n_layers, E, d, f), ("layers", "experts", "d_model", None)),
                "up": PD((n_layers, E, d, f), ("layers", "experts", "d_model", None)),
                "down": PD((n_layers, E, f, d), ("layers", "experts", None, "d_model"),
                           scale=(f ** -0.5) / (2 * c.num_layers) ** 0.5),
            }
        return {
            "gate": PD((n_layers, d, f), ("layers", "d_model", "ff")),
            "up": PD((n_layers, d, f), ("layers", "d_model", "ff")),
            "down": PD((n_layers, f, d), ("layers", "ff", "d_model"),
                       scale=(f ** -0.5) / (2 * c.num_layers) ** 0.5),
        }

    def param_defs(self) -> dict:
        c = self.cfg
        d, V, nL = c.d_model, c.vocab_size, c.num_layers
        blocks = {
            "attn": self._attn_defs(nL),
            "ffn": self._ffn_defs(nL),
            "ln1": PD((nL, d), ("layers", "d_model"), init="ones"),
            "ln2": PD((nL, d), ("layers", "d_model"), init="ones"),
        }
        if c.encoder_layers:
            blocks["xattn"] = self._attn_defs(nL)
            blocks["lnx"] = PD((nL, d), ("layers", "d_model"), init="ones")
        defs = {
            "blocks": blocks,
            "embed": PD((V, d), ("vocab", "d_model"), scale=0.02),
            "ln_f": PD((d,), ("d_model",), init="ones"),
        }
        if not c.tie_embeddings:
            defs["head"] = PD((d, V), ("d_model", "vocab"))
        if c.encoder_layers:
            eL = c.encoder_layers
            defs["encoder"] = {
                "attn": self._attn_defs(eL),
                "ffn": {
                    "gate": PD((eL, d, c.d_ff), ("layers", "d_model", "ff")),
                    "up": PD((eL, d, c.d_ff), ("layers", "d_model", "ff")),
                    "down": PD((eL, c.d_ff, d), ("layers", "ff", "d_model")),
                },
                "ln1": PD((eL, d), ("layers", "d_model"), init="ones"),
                "ln2": PD((eL, d), ("layers", "d_model"), init="ones"),
                "ln_f": PD((d,), ("d_model",), init="ones"),
            }
        return defs

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------

    def _block(self, lp: dict, x: jax.Array, positions: jax.Array,
               enc_out: Optional[jax.Array]) -> tuple[jax.Array, jax.Array]:
        c = self.cfg
        h = L.rms_norm(x, lp["ln1"], c.norm_eps)
        x = x + L.attention(lp["attn"], h, self.dims, positions=positions)
        if enc_out is not None:
            h = L.rms_norm(x, lp["lnx"], c.norm_eps)
            x = x + L.attention(lp["xattn"], h, self.dims, kv_x=enc_out)
        h = L.rms_norm(x, lp["ln2"], c.norm_eps)
        aux = jnp.float32(0.0)
        if c.moe is not None:
            y, aux = moe_lib.moe_ffn(lp["ffn"], h, c.moe)
            x = x + y
        else:
            x = x + L.swiglu(lp["ffn"], h)
        return x, aux

    def _stack(self, blocks: dict, x: jax.Array, positions: jax.Array,
               enc_out: Optional[jax.Array], gather: Gather,
               flush_segments=None) -> tuple[jax.Array, jax.Array]:
        gather = gather or _identity_gather
        body = functools.partial(self._apply_block, positions=positions,
                                 enc_out=enc_out, gather=gather)
        if self.cfg.remat:
            body = jax.checkpoint(body)

        def step(carry, lp):
            x, aux = carry
            # sequence-parallel residual stream: the per-layer remat residual
            # (this carry) is saved S/tp-sharded instead of replicated —
            # activation memory drops by the TP width.
            x = constrain(x, None, TP_AXIS, None)
            x2, a = body(lp, x)
            return (x2, aux + a), None

        if flush_segments is None:
            (x, aux), _ = jax.lax.scan(step, (x, jnp.float32(0.0)), blocks)
            return x, aux

        # bucketed backward overlap: the scan is split at bucket boundaries
        # and each segment's stacked params pass through a flush hook (a
        # custom_vjp identity whose backward syncs that bucket's gradients
        # cross-pod the moment its backward slice is produced — see
        # repro.core.overlap.flush_hook).  Forward math is identical to the
        # single scan: the segments traverse the same layers in order.
        bounds, hooks = flush_segments
        carry = (x, jnp.float32(0.0))
        for (lo, hi), hook in zip(bounds, hooks):
            seg = jax.tree.map(
                lambda a: jax.lax.slice_in_dim(a, lo, hi, axis=0), blocks)
            seg = hook(seg)
            carry, _ = jax.lax.scan(step, carry, seg)
        x, aux = carry
        return x, aux

    def _apply_block(self, lp, x, *, positions, enc_out, gather):
        return self._block(gather(lp), x, positions, enc_out)

    def _embed_inputs(self, params: dict, batch: dict) -> tuple[jax.Array, jax.Array, int]:
        """Token (+stub modality) embedding. Returns (x, positions, n_prefix)."""
        c = self.cfg
        tokens = batch["tokens"]
        x = jnp.take(params["embed"], tokens, axis=0)
        x = constrain(x, None, None, None)
        n_prefix = 0
        if c.vision_tokens:
            patches = batch["patch_embeds"].astype(x.dtype)   # (B, n_vis, d)
            x = jnp.concatenate([patches, x], axis=1)
            n_prefix = patches.shape[1]
        S = x.shape[1]
        positions = jnp.arange(S)
        if not c.rope_theta:  # sinusoidal absolute positions (whisper)
            x = x + L.sinusoidal_positions(positions, c.d_model).astype(x.dtype)[None]
        return x, positions, n_prefix

    def _encode(self, params: dict, batch: dict, gather: Gather) -> Optional[jax.Array]:
        c = self.cfg
        if not c.encoder_layers:
            return None
        src = batch["source_frames"]                     # (B, src_len, d) stub
        pos = jnp.arange(src.shape[1])
        x = src + L.sinusoidal_positions(pos, c.d_model).astype(src.dtype)[None]
        enc_dims = self.dims._replace(causal=False, window=None)
        gather = gather or _identity_gather

        def body(lp, x):
            lp = gather(lp)
            h = L.rms_norm(x, lp["ln1"], c.norm_eps)
            x = x + L.attention(lp["attn"], h, enc_dims, positions=None)
            h = L.rms_norm(x, lp["ln2"], c.norm_eps)
            return x + L.swiglu(lp["ffn"], h)

        if c.remat:
            body = jax.checkpoint(body)

        def step(x, lp):
            return body(lp, x), None

        enc = params["encoder"]
        blocks = {k: enc[k] for k in ("attn", "ffn", "ln1", "ln2")}
        x, _ = jax.lax.scan(step, x, blocks)
        return L.rms_norm(x, enc["ln_f"], c.norm_eps)

    def hidden_states(self, params: dict, batch: dict, *, gather: Gather = None,
                      flush_segments=None) -> tuple[jax.Array, jax.Array, int]:
        """Full-sequence forward to final-norm hidden states.

        `flush_segments` = (layer bounds, per-bucket flush hooks) splits the
        layer scan at gradient-bucket boundaries for backward-side sync
        overlap (see :meth:`_stack`); None keeps the single fused scan."""
        enc_out = self._encode(params, batch, gather)
        x, positions, n_prefix = self._embed_inputs(params, batch)
        x, aux = self._stack(params["blocks"], x, positions, enc_out, gather,
                             flush_segments=flush_segments)
        x = L.rms_norm(x, params["ln_f"], self.cfg.norm_eps)
        return x, aux, n_prefix

    def _head(self, params: dict) -> jax.Array:
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["head"]

    def loss(self, params: dict, batch: dict, *, gather: Gather = None,
             flush_segments=None) -> tuple[jax.Array, dict]:
        """batch["tokens"]: (B, S+1) — teacher forcing; extra stub inputs as
        required by the family. Returns (mean_local_loss, metrics)."""
        c = self.cfg
        tokens = batch["tokens"]
        inputs = {**batch, "tokens": tokens[:, :-1]}
        labels = tokens[:, 1:]
        x, aux, n_prefix = self.hidden_states(params, inputs, gather=gather,
                                              flush_segments=flush_segments)
        if n_prefix:
            x = x[:, n_prefix:]
        sum_loss, count = L.chunked_ce_loss(x, self._head(params), labels)
        loss = sum_loss / jnp.maximum(count, 1.0)
        metrics = {"ce_loss": loss, "aux_loss": aux, "tokens": count}
        if c.moe is not None:
            loss = loss + 0.01 * aux / c.num_layers
        return loss, metrics

    def logits(self, params: dict, batch: dict, *, gather: Gather = None) -> jax.Array:
        x, _, n_prefix = self.hidden_states(params, batch, gather=gather)
        if n_prefix:
            x = x[:, n_prefix:]
        out = (x @ self._head(params)).astype(jnp.float32)
        return constrain(out, None, None, TP_AXIS)

    # ------------------------------------------------------------------
    # decode (serve_step)
    # ------------------------------------------------------------------

    def cache_width(self, max_len: int) -> int:
        c = self.cfg
        if c.sliding_window is not None:
            return min(max_len, c.sliding_window)
        return max_len

    def cache_defs(self, batch_size: int, max_len: int) -> dict:
        c = self.cfg
        Dh = c.resolved_head_dim
        W = self.cache_width(max_len)
        nL = c.num_layers
        kv = ("layers", "batch", "seq", "kv_heads", None)
        defs = {
            "k": PD((nL, batch_size, W, c.num_kv_heads, Dh), kv, init="zeros"),
            "v": PD((nL, batch_size, W, c.num_kv_heads, Dh), kv, init="zeros"),
        }
        if c.encoder_layers:
            src = c.source_len
            defs["xk"] = PD((nL, batch_size, src, c.num_kv_heads, Dh), kv, init="zeros")
            defs["xv"] = PD((nL, batch_size, src, c.num_kv_heads, Dh), kv, init="zeros")
        return defs

    def decode_step(self, params: dict, cache: dict, pos: jax.Array,
                    tokens: jax.Array, *, gather: Gather = None) -> tuple[jax.Array, dict]:
        """One-token decode. tokens: (B, 1); pos: scalar int32 (tokens already
        in cache), or a (B,) int32 vector when continuous batching has each
        slot at its own depth.  Returns (logits (B,1,V), updated cache)."""
        c = self.cfg
        gather = gather or _identity_gather
        x = jnp.take(params["embed"], tokens, axis=0)
        if not c.rope_theta:
            if getattr(pos, "ndim", 0) >= 1:
                x = x + L.sinusoidal_positions(pos, c.d_model).astype(x.dtype)[:, None, :]
            else:
                x = x + L.sinusoidal_positions(jnp.full((1,), pos), c.d_model).astype(x.dtype)[None]
        ring = c.sliding_window is not None
        has_cross = bool(c.encoder_layers)

        # the caches are read-only inside the layer loop: each layer
        # yields its new K/V row, and the rows of all layers are written
        # into the donated caches after it, in place (put_kv_rows)
        def step(x, inp):
            lp, kc, vc, xk, xv = inp
            lp = gather(lp)
            h = L.rms_norm(x, lp["ln1"], c.norm_eps)
            a, k_row, v_row = L.decode_attention(lp["attn"], h, self.dims,
                                                 k_cache=kc, v_cache=vc,
                                                 pos=pos, ring=ring)
            x = x + a
            if has_cross:
                h = L.rms_norm(x, lp["lnx"], c.norm_eps)
                x = x + self._cross_decode(lp["xattn"], h, xk, xv)
            h = L.rms_norm(x, lp["ln2"], c.norm_eps)
            if c.moe is not None:
                y, _ = moe_lib.moe_ffn(lp["ffn"], h, c.moe)
                x = x + y
            else:
                x = x + L.swiglu(lp["ffn"], h)
            return x, (k_row, v_row)

        x, (k_rows, v_rows) = jax.lax.scan(
            step, x, (params["blocks"], cache["k"], cache["v"],
                      cache.get("xk"), cache.get("xv")))
        x = L.rms_norm(x, params["ln_f"], c.norm_eps)
        logits = (x @ self._head(params)).astype(jnp.float32)
        logits = constrain(logits, None, None, TP_AXIS)
        new_cache = dict(cache, k=L.put_kv_rows(cache["k"], k_rows, pos, ring),
                         v=L.put_kv_rows(cache["v"], v_rows, pos, ring))
        return logits, new_cache

    def _cross_decode(self, p: dict, x: jax.Array, xk: jax.Array, xv: jax.Array) -> jax.Array:
        dims = self.dims
        B = x.shape[0]
        H, KH, Dh = dims.num_heads, dims.num_kv_heads, dims.head_dim
        g = H // KH
        q = (x @ p["wq"]).reshape(B, 1, KH, g, Dh).astype(jnp.float32) * Dh ** -0.5
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, xk.astype(jnp.float32))
        p_attn = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p_attn, xv.astype(jnp.float32))
        o = o.reshape(B, 1, H * Dh).astype(x.dtype)
        return o @ p["wo"]

    def prefill(self, params: dict, batch: dict, *, gather: Gather = None
                ) -> tuple[jax.Array, dict]:
        """Run the full prompt, build the KV cache, return last-token logits."""
        c = self.cfg
        gather = gather or _identity_gather
        enc_out = self._encode(params, batch, gather)
        x, positions, n_prefix = self._embed_inputs(params, batch)
        B, S, _ = x.shape
        W = self.cache_width(S)
        dims = self.dims

        def body(lp, x):
            lp = gather(lp)
            h = L.rms_norm(x, lp["ln1"], c.norm_eps)
            q, k, v = L._project_qkv(lp["attn"], h, dims, positions)
            attn_out = self._prefill_attn(q, k, v)
            x = x + attn_out.reshape(B, S, -1) @ lp["attn"]["wo"]
            if enc_out is not None:
                h = L.rms_norm(x, lp["lnx"], c.norm_eps)
                x = x + L.attention(lp["xattn"], h, dims, kv_x=enc_out)
                xk = (enc_out @ lp["xattn"]["wk"]).reshape(B, -1, c.num_kv_heads, dims.head_dim)
                xv = (enc_out @ lp["xattn"]["wv"]).reshape(B, -1, c.num_kv_heads, dims.head_dim)
            else:
                xk = xv = None
            h = L.rms_norm(x, lp["ln2"], c.norm_eps)
            if c.moe is not None:
                y, _ = moe_lib.moe_ffn(lp["ffn"], h, c.moe)
                x = x + y
            else:
                x = x + L.swiglu(lp["ffn"], h)
            kc, vc = self._to_ring(k, W, S), self._to_ring(v, W, S)
            ys = (kc, vc) if xk is None else (kc, vc, xk, xv)
            return x, ys

        x, ys = jax.lax.scan(lambda x, lp: body(lp, x), x, params["blocks"])
        x = L.rms_norm(x, params["ln_f"], c.norm_eps)
        last = x[:, -1:, :]
        logits = (last @ self._head(params)).astype(jnp.float32)
        if c.encoder_layers:
            cache = {"k": ys[0], "v": ys[1], "xk": ys[2], "xv": ys[3]}
        else:
            cache = {"k": ys[0], "v": ys[1]}
        return logits, cache

    def _prefill_attn(self, q, k, v):
        from repro.kernels import ops
        o = ops.flash_attention(q, k, v, causal=self.dims.causal,
                                window=self.dims.window)
        return constrain(o, None, None, TP_AXIS, None)

    def _to_ring(self, k: jax.Array, W: int, S: int) -> jax.Array:
        """Arrange the last W positions into ring-buffer slot order."""
        if W >= S:
            return k
        lastW = k[:, S - W:]
        return jnp.roll(lastW, shift=S % W, axis=1)
