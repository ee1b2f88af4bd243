"""Decoder-only transformer LM covering the dense / moe / vlm families.

Layers are scan-stacked; the decode path supports the standard batched
KV cache, the paper's BifurcatedCache, the multi-prefix grouped (forest)
caches and the hierarchical prefix-trie caches (cascade decoding) — the
cache TYPE selects the decode path. VLM (internvl2) prepends stub
patch embeddings to the token embeddings — the image tokens become part of
the shared prefix and are covered by bifurcated attention like any other
context token.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import MeshRules, ModelConfig
from repro.core.kv_cache import BifurcatedCache, DecodeCache
from repro.distributed.sharding import constrain
from repro.models import blocks
from repro.models.blocks import (
    apply_mlp,
    apply_norm,
    attention_decode,
    attention_train,
    init_attention,
    init_mlp,
    init_norm,
)
from repro.models.moe import apply_moe, init_moe, moe_decode


def _init_layer(cfg: ModelConfig, key):
    k1, k2 = jax.random.split(key)
    layer = {
        "ln1": init_norm(cfg, cfg.d_model),
        "attn": init_attention(cfg, k1),
        "ln2": init_norm(cfg, cfg.d_model),
    }
    if cfg.moe is not None:
        layer["moe"] = init_moe(cfg, k2)
    else:
        layer["mlp"] = init_mlp(cfg, k2)
    return layer


class TransformerLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ---- params ----
    def init(self, key):
        cfg = self.cfg
        kE, kL, kH, kP = jax.random.split(key, 4)
        layer_keys = jax.random.split(kL, cfg.n_layers)
        layers = jax.vmap(functools.partial(_init_layer, cfg))(layer_keys)
        params = {
            "embed": blocks._dense_init(kE, (cfg.padded_vocab, cfg.d_model), scale_axis=1),
            "layers": layers,
            "final_norm": init_norm(cfg, cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = blocks._dense_init(kH, (cfg.padded_vocab, cfg.d_model), scale_axis=1)
        if cfg.family == "vlm":
            # stub frontend: a single projection standing in for InternViT's
            # mlp1 connector (patch embeddings are precomputed inputs).
            params["img_proj"] = blocks._dense_init(kP, (cfg.d_model, cfg.d_model))
        return params

    # ---- shared pieces ----
    def _embed(self, params, tokens):
        # the activation dtype every layer follows (weights cast to it at
        # use): bf16 for serving, float32 for a reference forward
        return jnp.take(params["embed"], tokens, axis=0).astype(
            jnp.dtype(self.cfg.dtype))

    def _unembed(self, params, x, rules):
        cfg = self.cfg
        table = params.get("lm_head", params["embed"])
        logits = x @ table.T.astype(x.dtype)
        logits = constrain(logits, rules, "batch", None, "tensor")
        if cfg.padded_vocab > cfg.vocab_size:
            pad_bias = jnp.where(
                jnp.arange(cfg.padded_vocab) < cfg.vocab_size, 0.0, -1e30
            ).astype(logits.dtype)
            logits = logits + pad_bias
        return logits

    def _layer_train(self, x, layer, rules, positions):
        cfg = self.cfg
        a = attention_train(cfg, layer["attn"], apply_norm(cfg, layer["ln1"], x),
                            rules=rules, positions=positions)
        x = x + a
        x = constrain(x, rules, "batch", None, None)
        h = apply_norm(cfg, layer["ln2"], x)
        if cfg.moe is not None:
            m, aux = apply_moe(cfg, layer["moe"], h, rules)
        else:
            m, aux = apply_mlp(cfg, layer["mlp"], h, rules), 0.0
        x = x + m
        x = constrain(x, rules, "batch", None, None)
        return x, aux

    # ---- training ----
    def train_logits(self, params, batch, rules: Optional[MeshRules], remat: str = "full"):
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, tokens)
        if cfg.family == "vlm":
            img = batch["patch_embeds"].astype(x.dtype) @ params["img_proj"].astype(x.dtype)
            x = jnp.concatenate([img, x], axis=1)
        x = constrain(x, rules, "batch", None, None)
        positions = jnp.arange(x.shape[1])

        def body(x, layer):
            x, aux = self._layer_train(x, layer, rules, positions)
            return x, aux

        if remat == "full":
            body = jax.checkpoint(body)
        elif remat == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            )
        x, auxes = lax.scan(body, x, params["layers"])
        x = apply_norm(cfg, params["final_norm"], x)
        logits = self._unembed(params, x, rules)
        if cfg.family == "vlm":  # only text positions produce logits
            logits = logits[:, batch["patch_embeds"].shape[1]:]
        return logits, jnp.sum(auxes)

    # ---- prefill (batched, standard cache out) ----
    def prefill(self, params, tokens, rules: Optional[MeshRules],
                patch_embeds: Optional[jnp.ndarray] = None):
        """Returns (last-position logits, DecodeCache holding the context)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        if cfg.family == "vlm" and patch_embeds is not None:
            img = patch_embeds.astype(x.dtype) @ params["img_proj"].astype(x.dtype)
            x = jnp.concatenate([img, x], axis=1)
        x = constrain(x, rules, "batch", None, None)
        positions = jnp.arange(x.shape[1])

        def body(x, layer):
            h = apply_norm(cfg, layer["ln1"], x)
            k, v = blocks.attention_prefill_kv(cfg, layer["attn"], h, positions)
            a = attention_train(cfg, layer["attn"], h, rules=rules, positions=positions)
            x = x + a
            h2 = apply_norm(cfg, layer["ln2"], x)
            if cfg.moe is not None:
                m, _ = apply_moe(cfg, layer["moe"], h2, rules)
            else:
                m = apply_mlp(cfg, layer["mlp"], h2, rules)
            x = x + m
            x = constrain(x, rules, "batch", None, None)
            return x, (k, v)

        x, (ks, vs) = lax.scan(body, x, params["layers"])
        x = apply_norm(cfg, params["final_norm"], x)
        logits = self._unembed(params, x[:, -1:], rules)[:, 0]
        cache = DecodeCache(k=ks, v=vs, length=jnp.asarray(x.shape[1], jnp.int32))
        return logits, cache

    def prefill_suffix(self, params, tokens, k_anc, v_anc,
                       rules: Optional[MeshRules], *, start: int):
        """Suffix-only prefill (cross-request prefix cache): ``tokens``
        (b, n) continue a cached prefix of ``start`` tokens whose per-layer
        rotated K/V — ``k_anc``/``v_anc``, (L, b, start, g, hd), exactly
        what ``prefill`` would have stacked — are fed as the context arm of
        each layer's attention. Only the n suffix tokens are embedded,
        projected and attended (cost O(n · (start + n)) instead of
        O((start + n)²)); the cached prefix is READ, never recomputed.

        Returns (last-position logits, DecodeCache over the SUFFIX only:
        k/v are (L, b, n, g, hd) at absolute positions start..start+n-1) —
        the token-slices a caller writes into its prefix cache."""
        cfg = self.cfg
        if cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"prefill_suffix supports dense/moe families, not "
                f"{cfg.family!r}")
        x = self._embed(params, tokens)
        x = constrain(x, rules, "batch", None, None)
        positions = start + jnp.arange(x.shape[1])

        def body(x, inp):
            layer, ka, va = inp
            h = apply_norm(cfg, layer["ln1"], x)
            a, k, v = blocks.attention_prefill_suffix(
                cfg, layer["attn"], h, ka, va, rules=rules,
                positions=positions)
            x = x + a
            h2 = apply_norm(cfg, layer["ln2"], x)
            if cfg.moe is not None:
                m, _ = apply_moe(cfg, layer["moe"], h2, rules)
            else:
                m = apply_mlp(cfg, layer["mlp"], h2, rules)
            x = x + m
            x = constrain(x, rules, "batch", None, None)
            return x, (k, v)

        x, (ks, vs) = lax.scan(body, x, (params["layers"], k_anc, v_anc))
        x = apply_norm(cfg, params["final_norm"], x)
        logits = self._unembed(params, x[:, -1:], rules)[:, 0]
        cache = DecodeCache(
            k=ks, v=vs,
            length=jnp.asarray(start + x.shape[1], jnp.int32))
        return logits, cache

    # ---- decode ----
    def decode_step(self, params, cache, tokens, rules: Optional[MeshRules],
                    *, impl: str = "einsum"):
        """tokens: (b, n) new token ids. Returns (logits (b, n, V), cache')."""
        cfg = self.cfg
        from repro.core.kv_cache import GroupedBifurcatedCache, PrefixTreeCache
        from repro.core.paged import PAGED_CACHE_FAMILIES
        from repro.core.quantized import (
            GroupedQuantBifurcatedCache,
            QuantBifurcatedCache,
            QuantPrefixTreeCache,
        )

        if isinstance(cache, PAGED_CACHE_FAMILIES):
            return self._decode_step_paged(params, cache, tokens, rules,
                                           impl=impl)
        if isinstance(cache, (PrefixTreeCache, QuantPrefixTreeCache)):
            return self._decode_step_tree(params, cache, tokens, rules,
                                          impl=impl)
        if isinstance(cache, (GroupedBifurcatedCache,
                              GroupedQuantBifurcatedCache)):
            return self._decode_step_forest(params, cache, tokens, rules,
                                            impl=impl)
        quant = isinstance(cache, QuantBifurcatedCache)
        bifurcated = isinstance(cache, BifurcatedCache) or quant
        x = self._embed(params, tokens)
        x = constrain(x, rules, "batch", None, None)
        if bifurcated:
            position = cache.context_len + cache.dec_length
            layer_caches = {
                "k_ctx": cache.k_ctx, "v_ctx": cache.v_ctx,
                "k_dec": cache.k_dec, "v_dec": cache.v_dec,
            }
            if quant:
                layer_caches["k_scale"] = cache.k_scale
                layer_caches["v_scale"] = cache.v_scale
        else:
            position = cache.length
            layer_caches = {"k": cache.k, "v": cache.v}

        def body(x, inp):
            layer, lcache = inp
            h = apply_norm(cfg, layer["ln1"], x)
            a, new_lcache = attention_decode(
                cfg, layer["attn"], h, lcache,
                position=position, rules=rules,
                bifurcated=bifurcated, impl=impl,
            )
            x = x + a
            h2 = apply_norm(cfg, layer["ln2"], x)
            if cfg.moe is not None:
                m = moe_decode(cfg, layer["moe"], h2, rules)
            else:
                m = apply_mlp(cfg, layer["mlp"], h2, rules)
            x = x + m
            return x, new_lcache

        x, new_caches = lax.scan(body, x, (params["layers"], layer_caches))
        x = apply_norm(cfg, params["final_norm"], x)
        logits = self._unembed(params, x, rules)
        n = tokens.shape[1]
        if bifurcated:  # both cache families: only the decode arm advances
            new_cache = dataclasses.replace(
                cache, k_dec=new_caches["k_dec"], v_dec=new_caches["v_dec"],
                dec_length=cache.dec_length + n,
            )
        else:
            new_cache = DecodeCache(
                k=new_caches["k"], v=new_caches["v"], length=cache.length + n
            )
        return logits, new_cache

    def _decode_step_forest(self, params, cache, tokens,
                            rules: Optional[MeshRules], *, impl: str):
        """Grouped-cache decode: b slots over G prefix segments, per-slot
        positions/depths. The forest bookkeeping (group_ids / ctx_lens /
        dec_lens) has no layer axis, so it rides the layer scan by closure;
        ``impl="kernel"`` lowers every layer-step to the grouped fused
        Pallas kernel."""
        cfg = self.cfg
        from repro.models.blocks import attention_decode_forest

        x = self._embed(params, tokens)
        x = constrain(x, rules, "batch", None, None)
        layer_caches = {
            "k_ctx": cache.k_ctx, "v_ctx": cache.v_ctx,
            "k_dec": cache.k_dec, "v_dec": cache.v_dec,
        }
        if hasattr(cache, "k_scale"):
            layer_caches["k_scale"] = cache.k_scale
            layer_caches["v_scale"] = cache.v_scale

        def body(x, inp):
            layer, lcache = inp
            h = apply_norm(cfg, layer["ln1"], x)
            a, new_lcache = attention_decode_forest(
                cfg, layer["attn"], h, lcache,
                group_ids=cache.group_ids, ctx_lens=cache.ctx_lens,
                dec_lens=cache.dec_lens, rules=rules, impl=impl,
            )
            x = x + a
            h2 = apply_norm(cfg, layer["ln2"], x)
            if cfg.moe is not None:
                m = moe_decode(cfg, layer["moe"], h2, rules)
            else:
                m = apply_mlp(cfg, layer["mlp"], h2, rules)
            x = x + m
            return x, new_lcache

        x, new_caches = lax.scan(body, x, (params["layers"], layer_caches))
        x = apply_norm(cfg, params["final_norm"], x)
        logits = self._unembed(params, x, rules)
        n = tokens.shape[1]
        new_cache = dataclasses.replace(
            cache, k_dec=new_caches["k_dec"], v_dec=new_caches["v_dec"],
            dec_lens=cache.dec_lens + n,
        )
        return logits, new_cache

    def _decode_step_tree(self, params, cache, tokens,
                          rules: Optional[MeshRules], *, impl: str):
        """Prefix-trie decode: b slots over N node segments, each slot
        attending over the concatenation of the nodes on its static-depth
        path. The trie bookkeeping (paths / node_lens / dec_lens and the
        per-slot total context length) has no layer axis, so it rides the
        layer scan by closure; ``impl="kernel"`` lowers every layer-step to
        the tree fused Pallas kernel."""
        cfg = self.cfg
        from repro.models.blocks import attention_decode_tree

        x = self._embed(params, tokens)
        x = constrain(x, rules, "batch", None, None)
        layer_caches = {
            "k_ctx": cache.k_ctx, "v_ctx": cache.v_ctx,
            "k_dec": cache.k_dec, "v_dec": cache.v_dec,
        }
        if hasattr(cache, "k_scale"):
            layer_caches["k_scale"] = cache.k_scale
            layer_caches["v_scale"] = cache.v_scale
        ctx_lens_b = cache.slot_context_lens()   # (b,) — once per step

        def body(x, inp):
            layer, lcache = inp
            h = apply_norm(cfg, layer["ln1"], x)
            a, new_lcache = attention_decode_tree(
                cfg, layer["attn"], h, lcache,
                paths=cache.paths, node_lens=cache.node_lens,
                ctx_lens_b=ctx_lens_b, dec_lens=cache.dec_lens,
                rules=rules, impl=impl,
            )
            x = x + a
            h2 = apply_norm(cfg, layer["ln2"], x)
            if cfg.moe is not None:
                m = moe_decode(cfg, layer["moe"], h2, rules)
            else:
                m = apply_mlp(cfg, layer["mlp"], h2, rules)
            x = x + m
            return x, new_lcache

        x, new_caches = lax.scan(body, x, (params["layers"], layer_caches))
        x = apply_norm(cfg, params["final_norm"], x)
        logits = self._unembed(params, x, rules)
        n = tokens.shape[1]
        new_cache = dataclasses.replace(
            cache, k_dec=new_caches["k_dec"], v_dec=new_caches["v_dec"],
            dec_lens=cache.dec_lens + n,
        )
        return logits, new_cache

    def _decode_step_paged(self, params, cache, tokens,
                           rules: Optional[MeshRules], *, impl: str):
        """Paged-store decode: b slots over a shared page POOL addressed
        through per-segment page tables — one step function for all three
        paged families (single / forest / trie), which differ only in the
        adapter views ``slot_paths`` / ``slot_dec_lens`` /
        ``slot_context_lens``. The tables / lengths / paths have no layer
        axis and ride the layer scan by closure; ``impl="kernel"`` lowers
        every layer-step to the paged page-walk Pallas kernel (only LIVE
        pages are DMA'd), ``impl="einsum"`` materializes dense slabs and
        runs the cascade einsum reference (escape hatch + oracle)."""
        cfg = self.cfg
        from repro.models.blocks import attention_decode_paged

        x = self._embed(params, tokens)
        x = constrain(x, rules, "batch", None, None)
        store = cache.store
        layer_caches = {
            "k_pages": store.k_pages, "v_pages": store.v_pages,
            "k_dec": cache.k_dec, "v_dec": cache.v_dec,
        }
        if hasattr(store, "k_scale_pages"):
            layer_caches["k_scale_pages"] = store.k_scale_pages
            layer_caches["v_scale_pages"] = store.v_scale_pages
        paths = cache.slot_paths()               # (depth, b)
        dec_lens = cache.slot_dec_lens()         # (b,)
        ctx_lens_b = cache.slot_context_lens()   # (b,) — once per step

        def body(x, inp):
            layer, lcache = inp
            h = apply_norm(cfg, layer["ln1"], x)
            a, new_lcache = attention_decode_paged(
                cfg, layer["attn"], h, lcache,
                page_tables=store.page_tables, seg_lens=store.seg_lens,
                paths=paths, ctx_lens_b=ctx_lens_b, dec_lens=dec_lens,
                rules=rules, impl=impl,
            )
            x = x + a
            h2 = apply_norm(cfg, layer["ln2"], x)
            if cfg.moe is not None:
                m = moe_decode(cfg, layer["moe"], h2, rules)
            else:
                m = apply_mlp(cfg, layer["mlp"], h2, rules)
            x = x + m
            return x, new_lcache

        x, new_caches = lax.scan(body, x, (params["layers"], layer_caches))
        x = apply_norm(cfg, params["final_norm"], x)
        logits = self._unembed(params, x, rules)
        n = tokens.shape[1]
        new_cache = cache.advance_decode(
            new_caches["k_dec"], new_caches["v_dec"], n)
        return logits, new_cache

    def decode_step_packed(self, params, cache, tokens, chunk_tokens,
                           rules: Optional[MeshRules], *,
                           k_fresh, v_fresh, buf_len, chunk_valid,
                           fresh_start, fresh_pos, fresh_path,
                           entries_per_launch: Optional[int] = None):
        """One PACKED heterogeneous step over a paged cache: the decode
        batch (``tokens`` (b, 1)) and ONE request's suffix-prefill chunk
        (``chunk_tokens`` (1, cp)) run through every layer in a single
        work-queue kernel launch per layer — no separate prefill dispatch.

        ``k_fresh``/``v_fresh`` are the per-layer (L, F*pm, g, hd) fresh-KV
        envelopes of the pending node (already-prefilled tokens in
        ``[:buf_len]``); the chunk's rotated K/V are spliced in in-trace
        and the updated envelopes return with the step. All chunk
        bookkeeping (lengths, positions, ancestor path) is runtime data —
        one compile serves every chunk of every admission.

        Returns (logits_dec (b, 1, V), logits_chunk (1, cp, V),
        new_cache, k_fresh', v_fresh')."""
        cfg = self.cfg
        from repro.models.blocks import attention_decode_packed

        x = self._embed(params, tokens)
        x = constrain(x, rules, "batch", None, None)
        x_c = self._embed(params, chunk_tokens)
        store = cache.store
        layer_caches = {
            "k_pages": store.k_pages, "v_pages": store.v_pages,
            "k_dec": cache.k_dec, "v_dec": cache.v_dec,
            "k_fresh": k_fresh, "v_fresh": v_fresh,
        }
        if hasattr(store, "k_scale_pages"):
            layer_caches["k_scale_pages"] = store.k_scale_pages
            layer_caches["v_scale_pages"] = store.v_scale_pages
        paths = cache.slot_paths()               # (depth, b)
        dec_lens = cache.slot_dec_lens()         # (b,)
        ctx_lens_b = cache.slot_context_lens()   # (b,) — once per step

        def body(carry, inp):
            x, x_c = carry
            layer, lcache = inp
            h = apply_norm(cfg, layer["ln1"], x)
            h_c = apply_norm(cfg, layer["ln1"], x_c)
            a, a_c, new_lcache = attention_decode_packed(
                cfg, layer["attn"], h, h_c, lcache,
                page_tables=store.page_tables, seg_lens=store.seg_lens,
                paths=paths, ctx_lens_b=ctx_lens_b, dec_lens=dec_lens,
                buf_len=buf_len, chunk_valid=chunk_valid,
                fresh_start=fresh_start, fresh_pos=fresh_pos,
                fresh_path=fresh_path, rules=rules,
                entries_per_launch=entries_per_launch,
            )
            x = x + a
            x_c = x_c + a_c
            h2 = apply_norm(cfg, layer["ln2"], x)
            h2_c = apply_norm(cfg, layer["ln2"], x_c)
            if cfg.moe is not None:
                m = moe_decode(cfg, layer["moe"], h2, rules)
                m_c = moe_decode(cfg, layer["moe"], h2_c, rules)
            else:
                m = apply_mlp(cfg, layer["mlp"], h2, rules)
                m_c = apply_mlp(cfg, layer["mlp"], h2_c, rules)
            x = x + m
            x_c = x_c + m_c
            return (x, x_c), new_lcache

        (x, x_c), new_caches = lax.scan(
            body, (x, x_c), (params["layers"], layer_caches))
        x = apply_norm(cfg, params["final_norm"], x)
        logits = self._unembed(params, x, rules)
        x_c = apply_norm(cfg, params["final_norm"], x_c)
        logits_c = self._unembed(params, x_c, rules)
        n = tokens.shape[1]
        new_cache = cache.advance_decode(
            new_caches["k_dec"], new_caches["v_dec"], n)
        return (logits, logits_c, new_cache,
                new_caches["k_fresh"], new_caches["v_fresh"])

    # ---- cache constructors (dry-run + serving) ----
    def make_paged_cache_spec(self, slots, n_segments, depth, node_capacity,
                              page_m=128, num_pages=None, dec_capacity=None,
                              ctx_quant: str = "none"):
        """Abstract paged trie cache (the general paged family) for the
        dry-run CLIs and sharding-spec builders. ``node_capacity`` is the
        per-segment TABLE envelope (tokens); storage is ``num_pages`` pool
        pages of ``page_m`` tokens (default: the full envelope)."""
        cfg = self.cfg
        from repro.core.paged import PagedPrefixTreeCache

        dec_capacity = dec_capacity or cfg.decode_capacity
        return PagedPrefixTreeCache.spec(
            cfg.n_layers, n_segments, depth, slots, node_capacity,
            dec_capacity, cfg.n_kv_heads_padded, cfg.kq_dim,
            page_m=page_m, num_pages=num_pages, ctx_quant=ctx_quant)

    def make_tree_cache_spec(self, slots, n_nodes, depth, node_capacity,
                             dec_capacity=None, ctx_quant: str = "none"):
        """Abstract PrefixTreeCache / QuantPrefixTreeCache for the dry-run
        CLIs and sharding-spec builders. ``depth`` is the static path-table
        height; everything else about the trie is runtime data."""
        cfg = self.cfg
        from repro.core.quantized import tree_cache_family

        dec_capacity = dec_capacity or cfg.decode_capacity
        return tree_cache_family(ctx_quant).spec(
            cfg.n_layers, n_nodes, depth, slots, node_capacity, dec_capacity,
            cfg.n_kv_heads_padded, cfg.kq_dim, ctx_layout=cfg.ctx_layout)

    def make_forest_cache_spec(self, slots, n_groups, ctx_capacity,
                               dec_capacity=None, ctx_quant: str = "none"):
        """Abstract GroupedBifurcatedCache / GroupedQuantBifurcatedCache for
        the dry-run CLIs and sharding-spec builders."""
        cfg = self.cfg
        from repro.core.quantized import forest_cache_family

        dec_capacity = dec_capacity or cfg.decode_capacity
        return forest_cache_family(ctx_quant).spec(
            cfg.n_layers, n_groups, slots, ctx_capacity, dec_capacity,
            cfg.n_kv_heads_padded, cfg.kq_dim, ctx_layout=cfg.ctx_layout)

    def make_cache_spec(self, batch, capacity, *, bifurcated, dec_capacity=None,
                        ctx_quant: str = "none"):
        cfg = self.cfg
        g, hd = cfg.n_kv_heads_padded, cfg.kq_dim
        if bifurcated:
            from repro.core.quantized import ctx_cache_family

            dec_capacity = dec_capacity or cfg.decode_capacity
            return ctx_cache_family(ctx_quant).spec(
                cfg.n_layers, batch, capacity - dec_capacity, dec_capacity,
                g, hd, ctx_layout=cfg.ctx_layout)
        return DecodeCache.spec(cfg.n_layers, batch, capacity, g, hd)
