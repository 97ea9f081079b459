"""Encoder-decoder backbone (seamless-m4t family).

Encoder consumes precomputed frame embeddings (speech frontend is a stub
per the assignment); decoder is causal with cross-attention to the encoder
memory. Both stacks are lax.scan'd segments with TBN-tileable projections.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import logical_constraint
from repro.nn import module as mod
from repro.nn.attention import Attention
from repro.nn.context import ModelContext
from repro.nn.embeddings import Embedding
from repro.nn.ffn import MLP
from repro.nn.linear import Dense
from repro.nn.norms import LayerNorm, RMSNorm


def _norm(cfg, ctx, name):
    cls = RMSNorm if cfg.norm == "rmsnorm" else LayerNorm
    return cls(cfg.d_model, ctx, name=name)


@dataclasses.dataclass
class EncBlock:
    cfg: ArchConfig
    ctx: ModelContext
    name: str = "enc"

    def __post_init__(self):
        cfg, c, d = self.cfg, self.ctx, self.cfg.d_model
        self.norm1 = _norm(cfg, c, f"{self.name}.norm1")
        self.attn = Attention(d, cfg.n_heads, cfg.n_kv, c, head_dim=cfg.head_dim,
                              name=f"{self.name}.attn", causal=False,
                              rope=cfg.rope_theta > 0, q_chunk=cfg.attn_chunk,
                              act_mode=cfg.attn_act)
        self.norm2 = _norm(cfg, c, f"{self.name}.norm2")
        self.ffn = MLP(d, cfg.d_ff, c, name=f"{self.name}.mlp",
                       gated=cfg.gated_mlp, activation=cfg.activation)

    def specs(self):
        return {"norm1": self.norm1.specs(), "attn": self.attn.specs(),
                "norm2": self.norm2.specs(), "ffn": self.ffn.specs()}

    def __call__(self, params, x, valid=None):
        # ``valid`` (B, S) masks padded frame columns in the serving path:
        # padded KEYS are excluded from every row's softmax (NEG_INF ->
        # exact-0 weight), so valid rows match an unpadded encode
        # byte-for-byte; padded QUERY rows produce garbage that position-
        # wise downstream ops never mix into valid rows.
        x = x + self.attn(params["attn"], self.norm1(params["norm1"], x),
                          kv_valid=valid)
        x = x + self.ffn(params["ffn"], self.norm2(params["norm2"], x))
        return logical_constraint(x, "act_batch", "act_res_seq", "act_embed")


@dataclasses.dataclass
class DecBlock:
    cfg: ArchConfig
    ctx: ModelContext
    name: str = "dec"

    def __post_init__(self):
        cfg, c, d = self.cfg, self.ctx, self.cfg.d_model
        self.norm1 = _norm(cfg, c, f"{self.name}.norm1")
        self.self_attn = Attention(d, cfg.n_heads, cfg.n_kv, c,
                                   head_dim=cfg.head_dim,
                                   name=f"{self.name}.self_attn", causal=True,
                                   rope=cfg.rope_theta > 0, q_chunk=cfg.attn_chunk,
                                   act_mode=cfg.attn_act)
        self.norm2 = _norm(cfg, c, f"{self.name}.norm2")
        self.cross_attn = Attention(d, cfg.n_heads, cfg.n_kv, c,
                                    head_dim=cfg.head_dim,
                                    name=f"{self.name}.cross_attn",
                                    causal=False, cross=True, rope=False,
                                    q_chunk=cfg.attn_chunk,
                                    act_mode=cfg.attn_act)
        self.norm3 = _norm(cfg, c, f"{self.name}.norm3")
        self.ffn = MLP(d, cfg.d_ff, c, name=f"{self.name}.mlp",
                       gated=cfg.gated_mlp, activation=cfg.activation)

    def specs(self):
        return {"norm1": self.norm1.specs(), "self_attn": self.self_attn.specs(),
                "norm2": self.norm2.specs(), "cross_attn": self.cross_attn.specs(),
                "norm3": self.norm3.specs(), "ffn": self.ffn.specs()}

    def __call__(self, params, x, memory):
        x = x + self.self_attn(params["self_attn"], self.norm1(params["norm1"], x))
        x = x + self.cross_attn(params["cross_attn"],
                                self.norm2(params["norm2"], x), kv_src=memory)
        x = x + self.ffn(params["ffn"], self.norm3(params["norm3"], x))
        return logical_constraint(x, "act_batch", "act_res_seq", "act_embed")

    def init_cache(self, batch, max_len, dtype):
        hd = self.self_attn.hd
        return {
            "k": jnp.zeros((batch, max_len, self.cfg.n_kv, hd), dtype),
            "v": jnp.zeros((batch, max_len, self.cfg.n_kv, hd), dtype),
            # cross K/V computed once at prefill
            "ck": None,
            "cv": None,
        }

    def decode_step(self, params, x, cache, lengths):
        import math as _math

        from repro.nn.attention import _attend_core

        h = self.norm1(params["norm1"], x)
        h, ck_, cv_ = self.self_attn.decode_step(
            params["self_attn"], h, cache["k"], cache["v"], lengths)
        x = x + h
        # cross attention against precomputed memory K/V
        mixer = self.cross_attn
        b = x.shape[0]
        h = self.norm2(params["norm2"], x)
        q = mixer.wq(params["cross_attn"]["wq"], h).reshape(
            b, 1, mixer.n_heads, mixer.hd)
        mask = jnp.ones((b, 1, cache["ck"].shape[1]), bool)
        out = _attend_core(mixer._group(q), cache["ck"], cache["cv"], mask,
                           1.0 / _math.sqrt(mixer.hd))
        h = mixer.wo(params["cross_attn"]["wo"],
                     out.reshape(b, 1, mixer.n_heads * mixer.hd))
        x = x + h
        x = x + self.ffn(params["ffn"], self.norm3(params["norm3"], x))
        return x, {**cache, "k": ck_, "v": cv_}


class EncDecModel:
    """seamless-m4t backbone: frame embeddings -> encoder -> text decoder."""

    def __init__(self, cfg: ArchConfig, ctx: Optional[ModelContext] = None):
        self.cfg = cfg
        self.ctx = ctx or ModelContext(policy=cfg.tbn)
        c = self.ctx
        d = cfg.d_model
        self.frame_proj = Dense(d, d, c, name="frame_proj",
                                logical=("embed", "embed"))
        self.embed = Embedding(cfg.vocab, d, c, name="dec_embed")
        self.enc_block = EncBlock(cfg, c)
        self.dec_block = DecBlock(cfg, c)
        self.enc_norm = _norm(cfg, c, "enc_norm")
        self.dec_norm = _norm(cfg, c, "dec_norm")
        self.head = Dense(d, cfg.vocab, c, name="lm_head", kind="head",
                          logical=("vocab", "embed"))

    def specs(self) -> mod.SpecTree:
        return {
            "frame_proj": self.frame_proj.specs(),
            "embed": self.embed.specs(),
            "enc": mod.stack_specs(self.enc_block.specs(), self.cfg.enc_layers),
            "dec": mod.stack_specs(self.dec_block.specs(), self.cfg.dec_layers),
            "enc_norm": self.enc_norm.specs(),
            "dec_norm": self.dec_norm.specs(),
            "head": self.head.specs(),
        }

    def init(self, key):
        return mod.init_params(self.specs(), key)

    def abstract(self):
        return mod.abstract_params(self.specs())

    def logical(self):
        return mod.logical_axes(self.specs())

    def _remat(self, f):
        if self.cfg.remat == "none":
            return f
        return jax.checkpoint(f)

    def encode(self, params, frames):
        """frames: (B, S_enc, d_model) precomputed frontend embeddings."""
        x = self.frame_proj(params["frame_proj"], frames)
        x = logical_constraint(x, "act_batch", "act_seq", "act_embed")

        if self.cfg.force_unroll:
            for j in range(self.cfg.enc_layers):
                pl = jax.tree.map(lambda v: v[j], params["enc"])
                x = self.enc_block(pl, x)
            return self.enc_norm(params["enc_norm"], x)

        def body(h, pl):
            return self._remat(lambda h, pl: (self.enc_block(pl, h), None))(h, pl)

        x, _ = jax.lax.scan(body, x, params["enc"])
        return self.enc_norm(params["enc_norm"], x)

    def decode(self, params, tokens, memory):
        x = self.embed(params["embed"], tokens)
        x = logical_constraint(x, "act_batch", "act_seq", "act_embed")

        if self.cfg.force_unroll:
            for j in range(self.cfg.dec_layers):
                pl = jax.tree.map(lambda v: v[j], params["dec"])
                x = self.dec_block(pl, x, memory)
            return self.dec_norm(params["dec_norm"], x)

        def body(h, pl):
            return self._remat(
                lambda h, pl: (self.dec_block(pl, h, memory), None)
            )(h, pl)

        x, _ = jax.lax.scan(body, x, params["dec"])
        return self.dec_norm(params["dec_norm"], x)

    def train_forward(self, params, batch) -> Tuple[jax.Array, Dict]:
        memory = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        s = tokens.shape[1]
        h = self.decode(params, tokens, memory)
        # full-seq logits + masked roll (keeps S divisible for SP sharding);
        # CE is batch-chunked + remat'd — the 256206-entry vocab does not
        # shard over 16 (odd), so unchunked (B, S, V) f32 logits would
        # replicate at 16 GB/device.
        targets = jnp.roll(tokens, -1, axis=1)
        valid = (jnp.arange(s) < s - 1).astype(jnp.float32)[None, :]
        mask = jnp.broadcast_to(valid, tokens.shape)
        b = tokens.shape[0]
        # 32-divisible sub-batches: see DecoderLM._ce_sum
        nb = b // 32 if (b % 32 == 0 and s * self.cfg.vocab >= 2**26) else 1

        def chunk_sum(hc, tc, mc):
            # re-pin batch sharding inside the chunk loop (see DecoderLM)
            hc = logical_constraint(hc, "act_batch", None, None)
            tc = logical_constraint(tc, "act_batch", None)
            mc = logical_constraint(mc, "act_batch", None)
            logits = self.head(params["head"], hc)
            logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
            gold = jnp.take_along_axis(
                logits.astype(jnp.float32), tc[..., None], axis=-1)[..., 0]
            return jnp.sum((logz - gold) * mc)

        if nb == 1:
            nll = chunk_sum(h, targets, mask)
        else:
            resh = lambda z: z.reshape(nb, b // nb, *z.shape[1:])
            body = jax.checkpoint(
                lambda acc, inp: (acc + chunk_sum(*inp), None)
            )
            nll, _ = jax.lax.scan(
                body, jnp.zeros((), jnp.float32),
                (resh(h), resh(targets), resh(mask)),
            )
        ce = nll / jnp.maximum(mask.sum(), 1.0)
        return ce, {"ce": ce, "aux": jnp.zeros(())}

    # ---------------- serving ----------------
    def prefill(self, params, batch, max_len: int):
        """Encode frames + run decoder prompt; build self+cross caches."""
        memory = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = self.embed(params["embed"], tokens)

        def body(h, pl):
            h2 = self.dec_block(pl, h, memory)
            # capture self-attn KV of the prompt + cross KV of the memory
            blk = self.dec_block
            hh = blk.norm1(pl["norm1"], h)
            _, (k, v) = blk.self_attn.prefill(pl["self_attn"], hh)
            t = memory.shape[1]
            ck = blk.cross_attn.wk(pl["cross_attn"]["wk"], memory).reshape(
                b, t, blk.cross_attn.n_kv, blk.cross_attn.hd)
            cv = blk.cross_attn.wv(pl["cross_attn"]["wv"], memory).reshape(
                b, t, blk.cross_attn.n_kv, blk.cross_attn.hd)
            pad = max_len - s
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            return h2, {"k": k, "v": v, "ck": ck, "cv": cv}

        if self.cfg.force_unroll:
            per_layer = []
            for j in range(self.cfg.dec_layers):
                pl = jax.tree.map(lambda v: v[j], params["dec"])
                x, cl = body(x, pl)      # (h2, this layer's caches)
                per_layer.append(cl)
            caches = jax.tree.map(lambda *ls: jnp.stack(ls), *per_layer)
        else:
            x, caches = jax.lax.scan(body, x, params["dec"])
        h = self.dec_norm(params["dec_norm"], x[:, -1:])
        logits = self.head(params["head"], h)
        return logits[:, 0], caches, jnp.full((b,), s, jnp.int32)

    def decode_step(self, params, tokens, caches, lengths, page_table=None,
                    active=None, cross_page_table=None, enc_lens=None):
        """One-token decode. ``page_table is None`` is the DENSE reference
        path (stacked per-slot rows from :meth:`prefill`) — the parity
        wall the paged engine path below is measured against, byte for
        byte. With ``page_table`` both cache families live in pool form:
        self-attention K/V scatter/gather through ``page_table`` exactly
        like DecoderLM, and cross-attention K/V are READ-ONLY pool pages
        written once by :meth:`write_cross`, viewed through
        ``cross_page_table`` and masked by ``enc_lens``."""
        if page_table is None:
            return self._decode_step_dense(params, tokens, caches, lengths)
        with jax.named_scope("embed"):
            x = self.embed(params["embed"], tokens)
        x, caches = self._walk_dec_paged(
            params, x, caches,
            lambda blk, pl, h, kl, vl, xk, xv: self._paged_layer(
                blk, pl, h, kl, vl, xk, xv, cross_page_table, enc_lens,
                lambda a: blk.self_attn.decode_step(
                    pl["self_attn"], a, kl, vl, lengths,
                    page_table=page_table, active=active,
                ),
            ),
        )
        with jax.named_scope("head"):
            h = self.dec_norm(params["dec_norm"], x)
            logits = self.head(params["head"], h)
        return logits[:, 0], caches, lengths + 1

    def _decode_step_dense(self, params, tokens, caches, lengths):
        x = self.embed(params["embed"], tokens)

        def body(h, xs):
            pl, cl = xs
            cl = jax.lax.optimization_barrier(cl)   # see lm.py decode_step
            h2, c2 = self.dec_block.decode_step(pl, h, cl, lengths)
            return h2, c2

        if self.cfg.force_unroll:
            per_layer = []
            for j in range(self.cfg.dec_layers):
                pl = jax.tree.map(lambda v: v[j], params["dec"])
                cl = jax.tree.map(lambda v: v[j], caches)
                x, c2 = body(x, (pl, cl))
                per_layer.append(c2)
            caches = jax.tree.map(lambda *ls: jnp.stack(ls), *per_layer)
        else:
            x, caches = jax.lax.scan(body, x, (params["dec"], caches))
        h = self.dec_norm(params["dec_norm"], x)
        logits = self.head(params["head"], h)
        return logits[:, 0], caches, lengths + 1

    # ------------------------------------------------------------------
    # ServableModel protocol (DESIGN.md §6.5): paged serving under the
    # shared BatchedEngine. The dense prefill/decode_step above stay
    # untouched as the parity reference.
    # ------------------------------------------------------------------
    has_full_attn = True        # decoder self-attention pages its K/V
    has_recurrent_state = False
    has_cross_attn = True       # engine stands up ENCODE phase + x-pool

    def cache_families(self):
        from repro.serve.servable import CacheFamily

        return (
            CacheFamily("self_attn", paged=True),
            CacheFamily("cross_attn", paged=True, read_only=True),
        )

    def init_caches(self, batch, max_len, dtype=jnp.bfloat16,
                    page_tokens=None, n_pages=None, cross_pages=None):
        """Pool-form decode caches: BOTH families are pages, addressed
        through separate tables — there is no dense ``(n_slots, T)`` row
        anywhere (the acceptance criterion for cross-attention K/V)."""
        if page_tokens is None:
            raise ValueError(
                "EncDecModel serves paged-only: pass page_tokens/n_pages/"
                "cross_pages (the dense reference path builds its caches "
                "via prefill, not init_caches)")
        L = self.cfg.dec_layers
        kv, hd = self.cfg.n_kv, self.dec_block.self_attn.hd
        z = lambda p: jnp.zeros((L, p, page_tokens, kv, hd), dtype)
        return {
            "self": {"k": z(n_pages), "v": z(n_pages)},
            "cross": {"k": z(cross_pages), "v": z(cross_pages)},
        }

    def encode_serve(self, params, frames, valid):
        """Fixed-shape encoder pass for the engine's ENCODE phase:
        ``frames`` (1, enc_tokens, d) zero-padded, ``valid`` (1,
        enc_tokens) marking real frames. Rows < the request's frame count
        are byte-identical to the unpadded :meth:`encode` (masked keys
        underflow to exact-0 softmax weight; everything else is
        position-wise)."""
        x = self.frame_proj(params["frame_proj"], frames)
        x = logical_constraint(x, "act_batch", "act_seq", "act_embed")
        if self.cfg.force_unroll:
            for j in range(self.cfg.enc_layers):
                pl = jax.tree.map(lambda v: v[j], params["enc"])
                x = self.enc_block(pl, x, valid=valid)
            return self.enc_norm(params["enc_norm"], x)

        def body(h, pl):
            return self.enc_block(pl, h, valid=valid), None

        x, _ = jax.lax.scan(body, x, params["enc"])
        return self.enc_norm(params["enc_norm"], x)

    def write_cross(self, params, memory, caches, xptab, positions, valid):
        """Project encoder ``memory`` (1, enc_tokens, d) to per-decoder-
        layer cross K/V and scatter into the cross pool through the
        admitted slot's page-table row ``xptab`` (1, x_npp). Runs ONCE per
        request at the end of its ENCODE phase; nothing writes these pages
        again until release."""
        from repro.nn.attention import scatter_pages

        blk = self.dec_block

        def per_layer(pl, ck_pool, cv_pool):
            k, v = blk.cross_attn.cross_kv(pl["cross_attn"], memory)
            ck_pool = scatter_pages(ck_pool, xptab, positions, k, valid)
            cv_pool = scatter_pages(cv_pool, xptab, positions, v, valid)
            return ck_pool, cv_pool

        xs = (params["dec"], caches["cross"]["k"], caches["cross"]["v"])
        if self.cfg.force_unroll:
            cks, cvs = [], []
            for j in range(self.cfg.dec_layers):
                a, b, c = (jax.tree.map(lambda v: v[j], t) for t in xs)
                ck, cv = per_layer(a, b, c)
                cks.append(ck)
                cvs.append(cv)
            ck, cv = jnp.stack(cks), jnp.stack(cvs)
        else:
            def body(_, layer_xs):
                return None, per_layer(*layer_xs)

            _, (ck, cv) = jax.lax.scan(body, None, xs)
        return {**caches, "cross": {"k": ck, "v": cv}}

    def _paged_layer(self, blk, pl, h, kl, vl, xk_l, xv_l, xptab, enc_lens,
                     self_step):
        """One decoder layer against pool caches: self-attn (via
        ``self_step``, which closes over decode vs extend), read-only
        cross-attend, FFN — same residual order as DecBlock.__call__."""
        with jax.named_scope("attn"):
            a = blk.norm1(pl["norm1"], h)
            a, kl, vl = self_step(a)
            h = h + a
            a = blk.norm2(pl["norm2"], h)
            h = h + blk.cross_attn.cross_attend(
                pl["cross_attn"], a, xk_l, xv_l, enc_lens, page_table=xptab,
            )
        with jax.named_scope("ffn"):
            h = h + blk.ffn(pl["ffn"], blk.norm3(pl["norm3"], h))
        return h, kl, vl

    def _walk_dec_paged(self, params, x, caches, step_fn):
        """Decoder layer loop for the paged tick: the stacked SELF pool
        rides in the scan CARRY with dynamic_update at the live layer (one
        buffer, no xs->ys double-buffering — see lm.py._walk_segments);
        the read-only CROSS pool rides as scan xs."""
        ks, vs = caches["self"]["k"], caches["self"]["v"]
        xks, xvs = caches["cross"]["k"], caches["cross"]["v"]

        def run_layer(pl, h, kl, vl, xk_l, xv_l):
            return step_fn(self.dec_block, pl, h, kl, vl, xk_l, xv_l)

        if self.cfg.force_unroll:
            nk, nv = [], []
            for j in range(self.cfg.dec_layers):
                pick = lambda v: v[j]
                x, kl, vl = run_layer(
                    jax.tree.map(pick, params["dec"]), x,
                    ks[j], vs[j], xks[j], xvs[j],
                )
                nk.append(kl)
                nv.append(vl)
            ks, vs = jnp.stack(nk), jnp.stack(nv)
        else:
            def body(carry, layer_xs):
                h, kfull, vfull, idx = carry
                pl, xk_l, xv_l = layer_xs
                with jax.named_scope("kv_layer"):
                    kl = jax.lax.dynamic_index_in_dim(kfull, idx, 0,
                                                      keepdims=False)
                    vl = jax.lax.dynamic_index_in_dim(vfull, idx, 0,
                                                      keepdims=False)
                    # barrier: stop LICM materializing converted copies of
                    # the whole stacked pool (see lm.py._walk_segments)
                    kl, vl = jax.lax.optimization_barrier((kl, vl))
                h, kl, vl = run_layer(pl, h, kl, vl, xk_l, xv_l)
                with jax.named_scope("kv_layer"):
                    kfull = jax.lax.dynamic_update_index_in_dim(
                        kfull, kl.astype(kfull.dtype), idx, 0)
                    vfull = jax.lax.dynamic_update_index_in_dim(
                        vfull, vl.astype(vfull.dtype), idx, 0)
                return (h, kfull, vfull, idx + 1), None

            (x, ks, vs, _), _ = jax.lax.scan(
                body, (x, ks, vs, jnp.int32(0)),
                (params["dec"], xks, xvs),
            )
        return x, {"self": {"k": ks, "v": vs},
                   "cross": {"k": xks, "v": xvs}}

    def extend(self, params, tokens, caches, lengths, n_new,
               page_table=None, cross_page_table=None, enc_lens=None):
        """Chunked-prefill step over the paged caches (same column
        semantics as DecoderLM.extend: padding columns never write and a
        slot's logits come from its last valid column)."""
        if page_table is None:
            raise ValueError("EncDecModel.extend is paged-only")
        b, c = tokens.shape
        positions = lengths[:, None] + jnp.arange(c)[None, :]
        valid = jnp.arange(c)[None, :] < n_new[:, None]
        with jax.named_scope("embed"):
            x = self.embed(params["embed"], tokens)
        x, caches = self._walk_dec_paged(
            params, x, caches,
            lambda blk, pl, h, kl, vl, xk, xv: self._paged_layer(
                blk, pl, h, kl, vl, xk, xv, cross_page_table, enc_lens,
                lambda a: blk.self_attn.extend(
                    pl["self_attn"], a, kl, vl, positions, valid,
                    page_table=page_table,
                ),
            ),
        )
        with jax.named_scope("head"):
            idx = jnp.clip(n_new - 1, 0, c - 1)
            h_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
            h = self.dec_norm(params["dec_norm"], h_last)
            logits = self.head(params["head"], h)
        return logits[:, 0], caches, lengths + n_new

    # ---- per-slot cache walkers: everything is paged, so these are ----
    # ---- passthroughs (the page tables carry all per-slot state)  ----
    def merge_caches(self, old, new, keep, paged=False):
        # pool writes were already confined in-kernel (active / valid
        # masks drop inactive slots' scatters); nothing to select per-slot
        return new

    def reset_slot_caches(self, caches, slot, paged=False):
        return caches           # stale pool rows are position-masked

    def snapshot_slot_caches(self, caches, slot):
        return None             # no non-paged family to pin

    def restore_slot_caches(self, caches, slot, snaps):
        return caches
