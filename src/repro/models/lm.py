"""Decoder LM covering the dense / MoE / SSM / hybrid / VLM families.

The layer stack is organised into *segments*: each segment is either a
lax.scan over N identical blocks (stacked params — keeps HLO size and
compile time independent of depth) or a single unrolled block (e.g. the
MoE first-dense layer, or the hybrid pattern remainder). Remat wraps each
scanned block.

One model object serves three entry points:
    train_forward(params, tokens, ...)    -> loss & metrics
    prefill(params, tokens, ...)          -> logits, caches
    decode_step(params, tokens, caches)   -> logits, caches
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

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
from repro.nn.moe import MoE
from repro.nn.norms import LayerNorm, RMSNorm
from repro.nn.rglru import RGLRUBlock
from repro.nn.ssm import Mamba2Block


def _norm(cfg: ArchConfig, ctx: ModelContext, dim: int, name: str):
    cls = RMSNorm if cfg.norm == "rmsnorm" else LayerNorm
    return cls(dim, ctx, name=name)


def _paged_attn(blk) -> bool:
    """Full-attention blocks page their K/V through the serving pool;
    windowed rings and recurrent state stay per-slot (they are bounded
    already and snapshot at prefix boundaries instead — DESIGN §6.2)."""
    return blk.kind == "attn" and not blk.cfg.window


def _map_block_cache(blk, fn, *subtrees):
    """Apply ``fn(leaf_block, *cache_subtrees)`` per leaf block, recursing
    through pattern super-blocks (whose cache is a {"b{i}": ...} dict) —
    the shared spine for every per-slot cache operation that must know
    which FAMILY a subtree belongs to (paged pool vs slot rows)."""
    if blk.kind == "pattern":
        return {
            f"b{i}": _map_block_cache(
                b, fn, *(t[f"b{i}"] for t in subtrees)
            )
            for i, b in enumerate(blk.blocks)
        }
    return fn(blk, *subtrees)


@dataclasses.dataclass
class Block:
    """One residual block: (attn|rec|ssm) + (mlp|moe), pre-norm."""

    cfg: ArchConfig
    ctx: ModelContext
    kind: str                       # "attn" | "rec" | "ssm"
    use_moe: bool
    name: str = "block"

    def __post_init__(self):
        cfg, ctx, d = self.cfg, self.ctx, self.cfg.d_model
        self.norm1 = _norm(cfg, ctx, d, f"{self.name}.norm1")
        if self.kind == "attn":
            self.mixer = Attention(
                d, cfg.n_heads, cfg.n_kv, ctx, head_dim=cfg.head_dim,
                name=f"{self.name}.attn", window=cfg.window,
                qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm,
                rope=cfg.rope_theta > 0, rope_theta=cfg.rope_theta or 10_000.0,
                q_chunk=cfg.attn_chunk, act_mode=cfg.attn_act,
            )
        elif self.kind == "rec":
            self.mixer = RGLRUBlock(d, ctx, name=f"{self.name}.rec")
        elif self.kind == "ssm":
            self.mixer = Mamba2Block(
                d, ctx, d_state=cfg.ssm.d_state, head_dim=cfg.ssm.head_dim,
                expand=cfg.ssm.expand, n_groups=cfg.ssm.n_groups,
                conv_width=cfg.ssm.conv_width, chunk=cfg.ssm.chunk,
                name=f"{self.name}.ssm",
            )
        else:
            raise ValueError(self.kind)
        self.has_ffn = self.kind != "ssm"   # mamba2 block is the whole layer
        if self.has_ffn:
            self.norm2 = _norm(cfg, ctx, d, f"{self.name}.norm2")
            if self.use_moe:
                m = cfg.moe
                self.ffn = MoE(
                    d, m.d_ff_expert or cfg.d_ff, m.n_experts, m.top_k, ctx,
                    n_shared=m.n_shared, name=f"{self.name}.moe",
                    gated=cfg.gated_mlp, activation=cfg.activation,
                )
            else:
                self.ffn = MLP(d, cfg.d_ff, ctx, name=f"{self.name}.mlp",
                               gated=cfg.gated_mlp, activation=cfg.activation)

    def specs(self) -> mod.SpecTree:
        out = {"norm1": self.norm1.specs(), "mixer": self.mixer.specs()}
        if self.has_ffn:
            out["norm2"] = self.norm2.specs()
            out["ffn"] = self.ffn.specs()
        return out

    def __call__(self, params, x, *, positions=None) -> Tuple[jax.Array, jax.Array]:
        aux = jnp.zeros((), jnp.float32)
        h = self.norm1(params["norm1"], x)
        if self.kind == "attn":
            h = self.mixer(params["mixer"], h, positions=positions)
        else:
            h = self.mixer(params["mixer"], h)
        x = x + h
        if self.has_ffn:
            h = self.norm2(params["norm2"], x)
            if self.use_moe:
                h, aux = self.ffn(params["ffn"], h)
            else:
                h = self.ffn(params["ffn"], h)
            x = x + h
        # sequence-parallel residual stream between blocks (see sharding.py)
        x = logical_constraint(x, "act_batch", "act_res_seq", "act_embed")
        return x, aux

    # ---------------- serving ----------------
    def init_cache(self, batch: int, max_len: int, dtype,
                   page_tokens: Optional[int] = None,
                   n_pages: Optional[int] = None):
        """Decode-cache allocation. With ``page_tokens``/``n_pages`` the
        full-attention K/V (and int8 scale) caches come up in POOL form —
        ``(n_pages, page_tokens, ...)`` pages addressed through the
        engine's page table — instead of dense ``(batch, max_len, ...)``
        slot rows. Windowed rings and recurrent state keep their per-slot
        layout either way."""
        if self.kind == "attn":
            hd = self.mixer.hd
            window = self.cfg.window
            kv = self.cfg.n_kv
            if page_tokens is not None and not window:
                lead = (n_pages, page_tokens)
            else:
                t = min(max_len, window) if window else max_len
                lead = (batch, t)
            if self.cfg.kv_dtype == "int8" and not window:
                return {
                    "k": jnp.zeros((*lead, kv, hd), jnp.int8),
                    "v": jnp.zeros((*lead, kv, hd), jnp.int8),
                    "ks": jnp.zeros((*lead, kv), jnp.float32),
                    "vs": jnp.zeros((*lead, kv), jnp.float32),
                }
            return {
                "k": jnp.zeros((*lead, kv, hd), dtype),
                "v": jnp.zeros((*lead, kv, hd), dtype),
            }
        return self.mixer.init_state(batch)

    def prefill(self, params, x, *, positions=None):
        h = self.norm1(params["norm1"], x)
        if self.kind == "attn":
            h, (k, v) = self.mixer.prefill(params["mixer"], h, positions)
            if self.cfg.kv_dtype == "int8" and not self.cfg.window:
                from repro.nn.attention import quantize_kv

                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                cache = {"k": kq, "v": vq, "ks": ks, "vs": vs}
            else:
                cache = {"k": k, "v": v}
        elif self.kind == "ssm":
            h, cache = self.mixer.forward_with_state(params["mixer"], h)
        else:  # rec: rerun scan, keep final state
            # full forward + final recurrent state via decode-equivalent scan
            h_out = self.mixer(params["mixer"], h)
            cache = self._rec_final_state(params["mixer"], h)
            h = h_out
        x = self._ffn(params, x + h)
        x = logical_constraint(x, "act_batch", "act_res_seq", "act_embed")
        return x, cache

    def _rec_final_state(self, params, h):
        """RG-LRU final (h, conv window) after a prefill pass."""
        mixer: RGLRUBlock = self.mixer
        xin = mixer.in_x(params["in_x"], h)
        xi = mixer._conv(params, xin)
        a, b = mixer._gates(params, xi)
        from repro.nn.rglru import _lru_scan

        hstates = _lru_scan(a, b)
        tail = xin[:, -(mixer.conv_width - 1):, :]
        pad = mixer.conv_width - 1 - tail.shape[1]
        if pad > 0:
            tail = jnp.pad(tail, ((0, 0), (pad, 0), (0, 0)))
        return {"h": hstates[:, -1], "conv": tail}

    def decode_step(self, params, x, cache, *, lengths,
                    page_table=None, active=None):
        with jax.named_scope(self.kind):
            h = self.norm1(params["norm1"], x)
            if self.kind == "attn":
                window = self.cfg.window
                if window:
                    # ring-buffer positions within the bounded window cache
                    slot = lengths % cache["k"].shape[1]
                    h, ck, cv = self._windowed_decode(
                        params["mixer"], h, cache, lengths, slot)
                    cache = {"k": ck, "v": cv}
                elif "ks" in cache:
                    h, cache = self.mixer.decode_step_quant(
                        params["mixer"], h, cache, lengths,
                        page_table=page_table, active=active,
                    )
                else:
                    h, ck, cv = self.mixer.decode_step(
                        params["mixer"], h, cache["k"], cache["v"], lengths,
                        page_table=page_table, active=active,
                    )
                    cache = {"k": ck, "v": cv}
            else:
                h, cache = self.mixer.decode_step(params["mixer"], h, cache)
            x = x + h
        return self._ffn(params, x), cache

    def _ffn(self, params, x):
        """The serving paths' second half of a block: norm2 + FFN/MoE and
        its residual, under the ``ffn`` scope."""
        if not self.has_ffn:
            return x
        with jax.named_scope("ffn"):
            h = self.norm2(params["norm2"], x)
            if self.use_moe:
                h, _ = self.ffn(params["ffn"], h)
            else:
                h = self.ffn(params["ffn"], h)
            return x + h

    def extend(self, params, x, cache, *, positions, valid, page_table=None):
        """Advance a (B, C) column block at per-slot offsets against the
        decode cache (chunked prefill). ``positions`` (B, C) are absolute
        token positions; ``valid`` (B, C) marks real columns — padding
        columns never write a cache row and never advance recurrent state,
        so a slot moves by exactly its count of valid columns (0 leaves it
        untouched up to dtype). ``page_table`` routes full-attention K/V
        writes through the paged pool.
        """
        with jax.named_scope(self.kind):
            h = self.norm1(params["norm1"], x)
            if self.kind == "attn":
                if self.cfg.window:
                    h, cache = self._windowed_extend(
                        params["mixer"], h, cache, positions, valid
                    )
                elif "ks" in cache:
                    h, cache = self.mixer.extend_quant(
                        params["mixer"], h, cache, positions, valid,
                        page_table=page_table,
                    )
                else:
                    h, ck, cv = self.mixer.extend(
                        params["mixer"], h, cache["k"], cache["v"],
                        positions, valid, page_table=page_table,
                    )
                    cache = {"k": ck, "v": cv}
            else:
                h, cache = self.mixer.extend(params["mixer"], h, cache,
                                             valid)
            x = x + h
        return self._ffn(params, x), cache

    def _windowed_extend(self, params, x, cache, positions, valid):
        """Chunked prefill against the sliding-window ring cache.

        Writes cannot be applied before the attend here: a column's write
        EVICTS the ring entry ``t`` positions back, which earlier columns
        of the same chunk may still need (it is inside their window). So
        queries attend the concatenation [old ring ; this chunk's fresh
        K/V] — in-chunk keys come from the fresh tensors — and the ring is
        updated afterwards. Ring writes keep one winner per ring slot (the
        last valid column of each residue class, ``j >= n_new - t``);
        shadowed and padding columns are dropped via an out-of-bounds
        index, never an unordered duplicate scatter.
        """
        import math as _math

        from repro.nn.attention import _attend_core, make_mask

        mixer: Attention = self.mixer
        b, c, _ = x.shape
        t = cache["k"].shape[1]
        window = self.cfg.window or t + 1
        q, k, v = mixer._qkv(params, x, None, positions, positions)

        # old-ring key positions, from the PRE-chunk frontier: ring slot j
        # holds the largest written position p <= lengths-1 with p ≡ j (t)
        last = positions[:, :1] - 1                  # (B, 1) frontier - 1
        ring = jnp.arange(t)[None, :]
        k_pos_old = last - jnp.mod(last - ring, t)   # (B, T); < 0 if empty
        k_cat = jnp.concatenate([cache["k"], k.astype(cache["k"].dtype)], axis=1)
        v_cat = jnp.concatenate([cache["v"], v.astype(cache["v"].dtype)], axis=1)
        mask_ring = make_mask(
            positions, k_pos_old, causal=True, window=window,
            k_valid=k_pos_old >= 0,
        )
        mask_chunk = make_mask(
            positions, positions, causal=True, window=window,
            k_valid=valid,
        )
        mask = jnp.concatenate([mask_ring, mask_chunk], axis=-1)
        out = _attend_core(
            mixer._group(q), k_cat, v_cat, mask, 1.0 / _math.sqrt(mixer.hd)
        )
        y = mixer.wo(params["wo"], out.reshape(b, c, mixer.n_heads * mixer.hd))

        n_new = jnp.sum(valid, axis=1)
        win = valid & (jnp.arange(c)[None, :] >= (n_new[:, None] - t))
        bidx = jnp.arange(b)[:, None]
        widx = jnp.where(win, positions % t, t)      # t == OOB -> dropped
        ck = cache["k"].at[bidx, widx].set(k.astype(cache["k"].dtype), mode="drop")
        cv = cache["v"].at[bidx, widx].set(v.astype(cache["v"].dtype), mode="drop")
        return y, {"k": ck, "v": cv}

    def _windowed_decode(self, params, x, cache, lengths, slot):
        """Sliding-window decode against a ring-buffer cache of size t<=W.

        Invariant: ring slot j holds the KV of the largest absolute position
        p <= lengths with p ≡ j (mod t). Prefill establishes this via a roll
        (see _pad_cache); each decode step maintains it.
        """
        import math as _math

        from repro.nn.attention import _attend_core

        mixer: Attention = self.mixer
        b = x.shape[0]
        t = cache["k"].shape[1]
        positions = lengths[:, None]
        q, k, v = mixer._qkv(params, x, None, positions, positions)
        idx = jnp.arange(b)
        ck = cache["k"].at[idx, slot].set(k[:, 0])
        cv = cache["v"].at[idx, slot].set(v[:, 0])
        ring = jnp.arange(t)[None, :]
        k_pos = lengths[:, None] - jnp.mod(lengths[:, None] - ring, t)
        valid = (k_pos >= 0) & (
            lengths[:, None] - k_pos < (self.cfg.window or t + 1)
        )
        mask = valid[:, None, :]
        out = _attend_core(
            mixer._group(q), ck, cv, mask, 1.0 / _math.sqrt(mixer.hd)
        )
        y = mixer.wo(params["wo"], out.reshape(b, 1, mixer.n_heads * mixer.hd))
        return y, ck, cv


@dataclasses.dataclass
class Segment:
    """A scanned stack of identical blocks, or one unrolled block."""

    block: Block
    n: int
    scanned: bool
    name: str

    def specs(self) -> mod.SpecTree:
        s = self.block.specs()
        return mod.stack_specs(s, self.n) if self.scanned else s


class DecoderLM:
    def __init__(self, cfg: ArchConfig, ctx: Optional[ModelContext] = None):
        self.cfg = cfg
        self.ctx = ctx or ModelContext(policy=cfg.tbn)
        c = self.ctx
        self.embed = Embedding(cfg.vocab, cfg.d_model, c, name="embed")
        self.segments: List[Segment] = self._build_segments()
        self.final_norm = _norm(cfg, c, cfg.d_model, "final_norm")
        if not cfg.tie_embeddings:
            self.head = Dense(cfg.d_model, cfg.vocab, c, name="lm_head",
                              kind="head", logical=("vocab", "embed"))

    def _build_segments(self) -> List[Segment]:
        cfg, c = self.cfg, self.ctx
        segs: List[Segment] = []
        if cfg.family == "ssm":
            segs.append(Segment(
                Block(cfg, c, "ssm", False, name="ssm_block"),
                cfg.n_layers, True, "stack"))
        elif cfg.family == "hybrid":
            pat = cfg.pattern
            n_super = len(pat)
            full, rem = divmod(cfg.n_layers, n_super)
            segs.append(Segment(
                _PatternBlock(cfg, c, pat, name="hybrid"),
                full, True, "stack"))
            for i in range(rem):
                segs.append(Segment(
                    Block(cfg, c, pat[i], False, name=f"tail{i}"),
                    1, False, f"tail{i}"))
        elif cfg.family in ("moe",):
            n = cfg.n_layers
            if cfg.moe.first_dense:
                segs.append(Segment(
                    Block(cfg, c, "attn", False, name="dense0"),
                    1, False, "dense0"))
                n -= 1
            segs.append(Segment(
                Block(cfg, c, "attn", True, name="moe_block"),
                n, True, "stack"))
        else:  # dense / vlm
            segs.append(Segment(
                Block(cfg, c, "attn", False, name="block"),
                cfg.n_layers, True, "stack"))
        return segs

    # ------------------------------------------------------------------
    def specs(self) -> mod.SpecTree:
        out = {
            "embed": self.embed.specs(),
            "final_norm": self.final_norm.specs(),
        }
        for i, seg in enumerate(self.segments):
            out[f"seg{i}"] = seg.specs()
        if not self.cfg.tie_embeddings:
            out["head"] = self.head.specs()
        return out

    def init(self, key) -> dict:
        return mod.init_params(self.specs(), key)

    def abstract(self) -> dict:
        return mod.abstract_params(self.specs())

    def logical(self) -> dict:
        return mod.logical_axes(self.specs())

    # ------------------------------------------------------------------
    def _embed_inputs(self, params, batch) -> jax.Array:
        x = self.embed(params["embed"], batch["tokens"])
        if self.cfg.modality == "vlm" and "image_embeds" in batch:
            # early fusion: image positions carry precomputed VQ embeddings
            m = batch["image_mask"][..., None]
            x = jnp.where(m, batch["image_embeds"].astype(x.dtype), x)
        return logical_constraint(x, "act_batch", "act_res_seq", "act_embed")

    def _remat(self, f):
        if self.cfg.remat == "none":
            return f
        if self.cfg.remat == "dots":
            return jax.checkpoint(
                f, policy=jax.checkpoint_policies.checkpoint_dots
            )
        return jax.checkpoint(f)

    def backbone(self, params, x, *, positions=None) -> Tuple[jax.Array, jax.Array]:
        aux_total = jnp.zeros((), jnp.float32)
        for i, seg in enumerate(self.segments):
            p = params[f"seg{i}"]
            if not seg.scanned:
                x, aux = seg.block(p, x, positions=positions)
                aux_total += aux
            elif self.cfg.force_unroll:
                # roofline path: every layer appears once in the HLO so
                # cost_analysis counts it (a while body is visited once)
                for j in range(seg.n):
                    pl = jax.tree.map(lambda v: v[j], p)
                    x, aux = seg.block(pl, x, positions=positions)
                    aux_total += aux
            else:
                def body(carry, pl):
                    h, auxa = carry
                    h, aux = seg.block(pl, h, positions=positions)
                    return (h, auxa + aux), None

                body = self._remat(body)
                (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), p)
        return self.final_norm(params["final_norm"], x), aux_total

    def logits(self, params, h) -> jax.Array:
        if self.cfg.tie_embeddings:
            out = self.embed.attend(params["embed"], h)
        else:
            out = self.head(params["head"], h)
        return logical_constraint(out, "act_batch", "act_seq", "act_vocab")

    def train_forward(self, params, batch) -> Tuple[jax.Array, Dict[str, Any]]:
        """Next-token CE loss. batch: tokens (B,S) [+ vlm extras]."""
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x = self._embed_inputs(params, batch)
        h, aux = self.backbone(params, x, positions=positions)
        # Full-sequence logits (S stays divisible for the sequence-parallel
        # sharding); the shifted last position is masked out of the loss.
        targets = jnp.roll(tokens, -1, axis=1)
        valid = (jnp.arange(s) < s - 1).astype(jnp.float32)[None, :]
        mask = batch.get("loss_mask", jnp.ones_like(targets, jnp.float32)) * valid
        nll = self._ce_sum(params, h, targets, mask)
        ce = nll / jnp.maximum(mask.sum(), 1.0)
        loss = ce + 0.01 * aux
        return loss, {"ce": ce, "aux": aux}

    def _ce_sum(self, params, h, targets, mask) -> jax.Array:
        """Summed token NLL. Batch-chunked + remat'd when large: the (B, S,
        V) f32 logits of a 150k-vocab model would otherwise be the single
        biggest training buffer (2.7-16 GB/device); chunking bounds it to
        one sub-batch and the backward recomputes per chunk."""
        b = h.shape[0]
        # chunk size stays a multiple of 32 so each sub-batch still shards
        # over the full (pod, data) DP extent of the 2-pod mesh
        nb = (
            b // 32
            if (b % 32 == 0 and h.shape[1] * self.cfg.vocab >= 2**26)
            else 1
        )
        if nb <= 1:
            return self._ce_sum_chunk(params, h, targets, mask)
        resh = lambda z: z.reshape(nb, b // nb, *z.shape[1:])

        def body(acc, inp):
            hc, tc, mc = inp
            return acc + self._ce_sum_chunk(params, hc, tc, mc), None

        body = jax.checkpoint(body)
        tot, _ = jax.lax.scan(
            body, jnp.zeros((), jnp.float32),
            (resh(h), resh(targets), resh(mask)),
        )
        return tot

    def _ce_sum_chunk(self, params, h, targets, mask) -> jax.Array:
        # scan xs lose their sharding through the chunk loop on the 3-axis
        # mesh — re-pin batch here or the (chunk, S, V) f32 logits replicate
        h = logical_constraint(h, "act_batch", None, None)
        targets = logical_constraint(targets, "act_batch", None)
        mask = logical_constraint(mask, "act_batch", None)
        logits = self.logits(params, h)
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(
            logits.astype(jnp.float32), targets[..., None], axis=-1
        )[..., 0]
        return jnp.sum((logz - gold) * mask)

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def init_caches(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                    page_tokens: Optional[int] = None,
                    n_pages: Optional[int] = None):
        """Decode caches for ``batch`` slots. With ``page_tokens``/
        ``n_pages`` the full-attention families allocate POOL form (one
        page index space shared by every layer — the engine's single page
        table addresses them all); other families are per-slot either
        way."""
        caches = []
        for seg in self.segments:
            c = seg.block.init_cache(
                batch, max_len, dtype,
                page_tokens=page_tokens, n_pages=n_pages,
            )
            if seg.scanned:
                c = jax.tree.map(
                    lambda v: jnp.broadcast_to(v[None], (seg.n, *v.shape)), c
                )
            caches.append(c)
        return caches

    # ---- per-slot cache surgery (engine-side bookkeeping helpers) ----
    def _leaf_blocks(self):
        for seg in self.segments:
            stack = [seg.block]
            while stack:
                b = stack.pop()
                if b.kind == "pattern":
                    stack.extend(b.blocks)
                else:
                    yield b

    @property
    def has_full_attn(self) -> bool:
        """Any full-attention layer -> the engine stands up a page pool."""
        return any(_paged_attn(b) for b in self._leaf_blocks())

    @property
    def has_recurrent_state(self) -> bool:
        """Any cache family that cannot be paged (SSM / RG-LRU carries,
        windowed rings) -> prefix reuse needs boundary snapshots."""
        return any(not _paged_attn(b) for b in self._leaf_blocks())

    # decoder-only: no encoder memory, no read-only cross-attention pool
    has_cross_attn = False

    def cache_families(self):
        """ServableModel cache-family descriptors (DESIGN.md §6.5)."""
        from repro.serve.servable import CacheFamily

        fams = []
        if self.has_full_attn:
            fams.append(CacheFamily("self_attn", paged=True))
        if self.has_recurrent_state:
            fams.append(CacheFamily("recurrent", paged=False))
        return tuple(fams)

    def reset_slot_caches(self, caches, slot, paged: bool = False):
        """Zero one slot's rows across the per-slot cache families:
        recurrent/SSM state MUST restart from zeros (extend continues from
        the slot's carry), windowed rings are cleared for hygiene. Paged
        pool leaves are left alone — their pages are shared or about to be
        remapped, and stale rows are position-masked."""
        out = []
        for seg, c in zip(self.segments, caches):
            ax = 1 if seg.scanned else 0

            def per_block(blk, ct, ax=ax):
                if paged and _paged_attn(blk):
                    return ct
                return jax.tree.map(
                    lambda v: v.at[(slice(None),) * ax + (slot,)].set(
                        jnp.zeros((), v.dtype)
                    ),
                    ct,
                )

            out.append(_map_block_cache(seg.block, per_block, c))
        return out

    def snapshot_slot_caches(self, caches, slot):
        """One slot's NON-PAGED cache state as a standalone pytree — the
        prefix trie pins this at page boundaries. Full-attention entries
        are None (their prefix lives in pool pages); recurrent mixers own
        their slice semantics (ssm/rglru ``snapshot_state``); windowed
        rings copy the slot's ring rows."""
        snaps = []
        for seg, c in zip(self.segments, caches):
            ax = 1 if seg.scanned else 0

            def per_block(blk, ct, ax=ax):
                if blk.kind in ("rec", "ssm"):
                    return blk.mixer.snapshot_state(ct, slot, axis=ax)
                if blk.kind == "attn" and blk.cfg.window:
                    return mod.slice_slot_rows(ct, slot, ax)
                return None

            snaps.append(_map_block_cache(seg.block, per_block, c))
        return snaps

    def restore_slot_caches(self, caches, slot, snaps):
        """Map a pinned snapshot back into a slot (prefix-hit admission).
        None entries (full-attention families) pass through — the page
        table, not the pool contents, carries their prefix."""
        out = []
        for seg, c, s in zip(self.segments, caches, snaps):
            ax = 1 if seg.scanned else 0
            if s is None:
                out.append(c)
                continue

            def per_block(blk, ct, st, ax=ax):
                if st is None:
                    return ct
                if blk.kind in ("rec", "ssm"):
                    return blk.mixer.restore_state(ct, slot, st, axis=ax)
                return mod.set_slot_rows(ct, slot, st, ax)

            out.append(_map_block_cache(seg.block, per_block, c, s))
        return out

    def prefill(self, params, batch, max_len: int):
        """Run the prompt, return (last-position logits, caches, lengths)."""
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x = self._embed_inputs(params, batch)
        caches = []
        for i, seg in enumerate(self.segments):
            p = params[f"seg{i}"]
            if not seg.scanned:
                x, cache = seg.block.prefill(p, x, positions=positions)
            elif self.cfg.force_unroll:
                per_layer = []
                for j in range(seg.n):
                    pl = jax.tree.map(lambda v: v[j], p)
                    x, cl = seg.block.prefill(pl, x, positions=positions)
                    per_layer.append(cl)
                cache = jax.tree.map(lambda *ls: jnp.stack(ls), *per_layer)
            else:
                def body(h, pl):
                    h2, cache = seg.block.prefill(pl, h, positions=positions)
                    return h2, cache

                x, cache = jax.lax.scan(body, x, p)
            # pad attention caches out to max_len
            cache = self._pad_cache(seg, cache, max_len, prompt_len=s)
            caches.append(cache)
        h = self.final_norm(params["final_norm"], x[:, -1:])
        logits = self.logits(params, h)
        lengths = jnp.full((b,), s, jnp.int32)
        return logits[:, 0], caches, lengths

    def _pad_cache(self, seg, cache, max_len, prompt_len=None):
        """Grow attention caches to serving size; set up window ring order."""
        window = self.cfg.window
        t_axis = 2 if seg.scanned else 1

        def pad_kv(v):
            t = v.shape[t_axis]
            target = min(max_len, window) if window else max_len
            if t > target:  # window: keep last `target` entries...
                sl = [slice(None)] * v.ndim
                sl[t_axis] = slice(t - target, t)
                v = v[tuple(sl)]
                # ...and roll so slot j holds position p ≡ j (mod target)
                if prompt_len is not None:
                    v = jnp.roll(v, prompt_len % target, axis=t_axis)
            elif t < target:
                widths = [(0, 0)] * v.ndim
                widths[t_axis] = (0, target - t)
                v = jnp.pad(v, widths)
                if window and prompt_len is not None and t == prompt_len:
                    # short prompt in a ring cache: entries already at slots
                    # 0..t-1 == their positions mod target (t <= target).
                    pass
            return v

        def rec(c):
            if isinstance(c, dict) and "k" in c and "v" in c:
                # every leaf (k/v codes and ks/vs scales) has the time
                # axis at the same index, so one pad rule covers them all
                return {name: pad_kv(vv) for name, vv in c.items()}
            if isinstance(c, dict):
                return {k: rec(v) for k, v in c.items()}
            return c

        return rec(cache)

    def _walk_segments(self, params, x, caches, step_fn):
        """Shared serving segment loop for decode_step/extend.

        ``step_fn(block, layer_params, x, cache) -> (x, cache)`` is applied
        once per layer. For scanned segments the stacked cache rides in the
        CARRY and is updated with a dynamic_update_slice at the live layer
        index: while-loop carries alias in place, so the step holds ONE
        cache buffer. (As scan xs->ys the cache double-buffers — an extra
        10.7 GB/device for the 32B config at 32k x 128.)
        """
        new_caches = []
        for i, seg in enumerate(self.segments):
            p = params[f"seg{i}"]
            cache = caches[i]
            if not seg.scanned:
                x, cache = step_fn(seg.block, p, x, cache)
            elif self.cfg.force_unroll:
                per_layer = []
                for j in range(seg.n):
                    pl = jax.tree.map(lambda v: v[j], p)
                    cl = jax.tree.map(lambda v: v[j], cache)
                    x, c2 = step_fn(seg.block, pl, x, cl)
                    per_layer.append(c2)
                cache = jax.tree.map(lambda *ls: jnp.stack(ls), *per_layer)
            else:
                def body(carry, pl):
                    h, full, idx = carry
                    with jax.named_scope("kv_layer"):
                        cl = jax.tree.map(
                            lambda v: jax.lax.dynamic_index_in_dim(
                                v, idx, 0, keepdims=False
                            ),
                            full,
                        )
                        # Barrier: stops XLA hoisting per-layer cache
                        # converts out of the loop (LICM would materialize
                        # an f32 copy of the ENTIRE stacked KV cache). int8
                        # codes cannot be promoted, so only float cache
                        # leaves need it (§Perf iteration B3: neutral, kept
                        # for clarity).
                        needs_barrier = any(
                            jnp.issubdtype(v.dtype, jnp.floating)
                            for v in jax.tree_util.tree_leaves(cl)
                            if v.ndim >= 4
                        )
                        if needs_barrier:
                            cl = jax.lax.optimization_barrier(cl)
                    h2, c2 = step_fn(seg.block, pl, h, cl)
                    with jax.named_scope("kv_layer"):
                        full = jax.tree.map(
                            lambda v, n: jax.lax.dynamic_update_index_in_dim(
                                v, n.astype(v.dtype), idx, 0
                            ),
                            full, c2,
                        )
                    return (h2, full, idx + 1), None

                (x, cache, _), _ = jax.lax.scan(
                    body, (x, cache, jnp.int32(0)), p
                )
            new_caches.append(cache)
        return x, new_caches

    def decode_step(self, params, tokens, caches, lengths,
                    page_table=None, active=None):
        """tokens: (B, 1) -> (logits (B, vocab), new caches).

        ``page_table`` (B, npp) routes full-attention K/V through the
        paged pool; ``active`` (B,) confines those pool writes to live
        decoding slots (per-slot families are confined by the engine's
        merge instead)."""
        with jax.named_scope("embed"):
            x = self.embed(params["embed"], tokens)
        x, new_caches = self._walk_segments(
            params, x, caches,
            lambda blk, pl, h, cl: blk.decode_step(
                pl, h, cl, lengths=lengths,
                page_table=page_table, active=active,
            ),
        )
        with jax.named_scope("head"):
            h = self.final_norm(params["final_norm"], x)
            logits = self.logits(params, h)
        return logits[:, 0], new_caches, lengths + 1

    def extend(self, params, tokens, caches, lengths, n_new,
               page_table=None):
        """Chunked-prefill step: advance each slot by its next n_new[b]
        prompt tokens against the shared decode caches.

        tokens: (B, C) — column j of slot b carries the prompt token at
        absolute position lengths[b] + j; columns >= n_new[b] are padding
        (no cache write, no state advance, output discarded). Returns
        (logits at each slot's LAST VALID column (B, vocab), caches,
        lengths + n_new); a slot with n_new == 0 is untouched and its
        logits row is meaningless. ``page_table`` routes full-attention
        K/V through the paged pool.
        """
        b, c = tokens.shape
        positions = lengths[:, None] + jnp.arange(c)[None, :]
        valid = jnp.arange(c)[None, :] < n_new[:, None]
        with jax.named_scope("embed"):
            x = self.embed(params["embed"], tokens)
        x, new_caches = self._walk_segments(
            params, x, caches,
            lambda blk, pl, h, cl: blk.extend(
                pl, h, cl, positions=positions, valid=valid,
                page_table=page_table,
            ),
        )
        with jax.named_scope("head"):
            idx = jnp.clip(n_new - 1, 0, c - 1)
            h_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
            h = self.final_norm(params["final_norm"], h_last)
            logits = self.logits(params, h)
        return logits[:, 0], new_caches, lengths + n_new

    def merge_caches(self, old, new, keep, paged: bool = False):
        """Per-slot cache select: rows where ``keep`` (B,) is True take the
        new cache, others keep the old — the engine uses this to confine a
        batched decode step to its live-decoding slots (a prefilling
        neighbor's caches must not see the step's garbage writes).

        Paged pool leaves have no slot axis to select on; their writes
        were already confined in-kernel (the ``active`` mask drops an
        inactive slot's scatter), so with ``paged`` the full-attention
        families take the new pool wholesale."""
        merged = []
        for seg, o, n in zip(self.segments, old, new):
            ax = 1 if seg.scanned else 0

            def per_block(blk, ov_tree, nv_tree, ax=ax):
                if paged and _paged_attn(blk):
                    return nv_tree

                def sel(ov, nv, ax=ax):
                    shape = [1] * ov.ndim
                    shape[ax] = keep.shape[0]
                    return jnp.where(
                        keep.reshape(shape), nv.astype(ov.dtype), ov
                    )

                return jax.tree.map(sel, ov_tree, nv_tree)

            merged.append(_map_block_cache(seg.block, per_block, o, n))
        return merged


@dataclasses.dataclass
class _PatternBlock:
    """Super-block: the hybrid cycle (e.g. rec, rec, attn) as one unit."""

    cfg: ArchConfig
    ctx: ModelContext
    pattern: Tuple[str, ...]
    name: str = "pattern"

    def __post_init__(self):
        self.blocks = [
            Block(self.cfg, self.ctx, kind, False, name=f"{self.name}.{i}_{kind}")
            for i, kind in enumerate(self.pattern)
        ]
        self.kind = "pattern"

    def specs(self) -> mod.SpecTree:
        return {f"b{i}": b.specs() for i, b in enumerate(self.blocks)}

    def __call__(self, params, x, *, positions=None):
        aux = jnp.zeros((), jnp.float32)
        for i, b in enumerate(self.blocks):
            x, a = b(params[f"b{i}"], x, positions=positions)
            aux += a
        return x, aux

    def init_cache(self, batch, max_len, dtype, page_tokens=None,
                   n_pages=None):
        return {
            f"b{i}": b.init_cache(batch, max_len, dtype,
                                  page_tokens=page_tokens, n_pages=n_pages)
            for i, b in enumerate(self.blocks)
        }

    def _forward(self, method, params, x, cache, **kw):
        """THE single serving call site through the pattern: thread the
        residual stream through each sub-block's ``method`` and collect
        the per-sub-block cache subtrees under the ``b{i}`` keys every
        cache walker recurses on (``_map_block_cache``). ``cache=None``
        (prefill) means the sub-block builds its cache instead of
        consuming one."""
        out = {}
        for i, b in enumerate(self.blocks):
            args = (x,) if cache is None else (x, cache[f"b{i}"])
            x, out[f"b{i}"] = getattr(b, method)(params[f"b{i}"], *args, **kw)
        return x, out

    def prefill(self, params, x, *, positions=None):
        return self._forward("prefill", params, x, None, positions=positions)

    def decode_step(self, params, x, cache, *, lengths,
                    page_table=None, active=None):
        return self._forward("decode_step", params, x, cache,
                             lengths=lengths, page_table=page_table,
                             active=active)

    def extend(self, params, x, cache, *, positions, valid, page_table=None):
        return self._forward("extend", params, x, cache, positions=positions,
                             valid=valid, page_table=page_table)
