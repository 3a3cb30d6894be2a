"""The composable decoder: every assigned architecture is built from the
same scan-over-layers skeleton, dispatched on ``cfg.block_kind``.

Design points (DESIGN.md §5/§6):
- scan over stacked layer params keeps HLO size O(1) in depth (62-100 layer
  configs compile in minutes on one host core);
- per-layer *flags* (gemma local/global, hymba SWA/global) ride along as
  scanned arrays so heterogeneous attention patterns share one block body;
- heterogeneous *structures* (llama-vision self/cross, xlstm mLSTM/sLSTM)
  scan over repeating UNITS with sub-stacked params;
- decode uses dense (non-blocked) attention so GSPMD can shard the KV axis
  (flash-decoding emerges from the sharded softmax reductions);
- MoE layers run in a shard_map island (models/moe.py) when a mesh is
  present: expert-parallel over 'model', ZeRO-gathered over 'data'.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from . import attention as A
from . import layers as L
from . import moe as M
from . import ssm as S
from .sharding import Sharder

Params = Any


# ==========================================================================
# builder
# ==========================================================================
class Model:
    def __init__(self, cfg: ArchConfig, mesh=None, remat: bool = True):
        self.cfg = cfg
        self.mesh = mesh
        self.remat = remat and cfg.n_layers > 2
        self.sh = Sharder(mesh)

    # --------------------------- init ------------------------------------
    def init_params(self, rng) -> Params:
        cfg = self.cfg
        k_embed, k_layers, k_out, k_extra = jax.random.split(rng, 4)
        params: dict[str, Any] = {}

        if cfg.n_codebooks:
            ks = jax.random.split(k_embed, cfg.n_codebooks)
            params["embed"] = {"table": jnp.stack(
                [L.init_embedding(k, cfg.vocab_size, cfg.d_model)["table"]
                 for k in ks])}          # (nq, V, d)
        else:
            params["embed"] = L.init_embedding(k_embed, cfg.vocab_size, cfg.d_model)

        params["final_norm"] = L.init_rmsnorm(cfg.d_model)
        if not cfg.tie_embeddings:
            if cfg.n_codebooks:
                ks = jax.random.split(k_out, cfg.n_codebooks)
                params["lm_head"] = {"table": jnp.stack(
                    [L.init_embedding(k, cfg.vocab_size, cfg.d_model)["table"]
                     for k in ks])}
            else:
                params["lm_head"] = L.init_embedding(k_out, cfg.vocab_size, cfg.d_model)
        if cfg.n_meta_tokens:
            params["meta_tokens"] = L._init(k_extra, (cfg.n_meta_tokens, cfg.d_model),
                                            scale=0.02)

        params.update(self._init_layers(k_layers))
        return params

    def _stack(self, key, n: int, init_one):
        keys = jax.random.split(key, n)
        return jax.tree.map(lambda *xs: jnp.stack(xs), *[init_one(k) for k in keys])

    def _init_layers(self, key) -> dict:
        cfg = self.cfg
        kind = cfg.block_kind
        k1, k2 = jax.random.split(key)

        if kind in ("gqa", "gemma", "musicgen"):
            def one(k):
                ka, km = jax.random.split(k)
                return {"ln1": L.init_rmsnorm(cfg.d_model),
                        "attn": A.init_attention(ka, cfg.d_model, cfg.n_heads,
                                                 cfg.n_kv_heads, cfg.head_dim,
                                                 cfg.qk_norm),
                        "ln2": L.init_rmsnorm(cfg.d_model),
                        "mlp": L.init_mlp(km, cfg.d_model, cfg.d_ff, cfg.mlp_gated)}
            return {"layers": self._stack(k1, cfg.n_layers, one)}

        if kind == "gqa_moe":
            def one(k):
                ka, km = jax.random.split(k)
                return {"ln1": L.init_rmsnorm(cfg.d_model),
                        "attn": A.init_attention(ka, cfg.d_model, cfg.n_heads,
                                                 cfg.n_kv_heads, cfg.head_dim,
                                                 cfg.qk_norm),
                        "ln2": L.init_rmsnorm(cfg.d_model),
                        "moe": M.init_moe(km, cfg.d_model, cfg.d_ff_expert,
                                          cfg.n_experts, cfg.n_shared_experts)}
            return {"layers": self._stack(k1, cfg.n_layers, one)}

        if kind == "mla_moe":
            def mla_kwargs():
                return dict(kv_lora=cfg.kv_lora_rank, nope_dim=cfg.qk_nope_dim,
                            rope_dim=cfg.qk_rope_dim, v_dim=cfg.v_head_dim)

            def one_moe(k):
                ka, km = jax.random.split(k)
                return {"ln1": L.init_rmsnorm(cfg.d_model),
                        "attn": A.init_mla(ka, cfg.d_model, cfg.n_heads, **mla_kwargs()),
                        "ln2": L.init_rmsnorm(cfg.d_model),
                        "moe": M.init_moe(km, cfg.d_model, cfg.d_ff_expert,
                                          cfg.n_experts, cfg.n_shared_experts,
                                          d_ff_shared=cfg.d_ff_expert * max(cfg.n_shared_experts, 1))}

            def one_dense(k):
                ka, km = jax.random.split(k)
                return {"ln1": L.init_rmsnorm(cfg.d_model),
                        "attn": A.init_mla(ka, cfg.d_model, cfg.n_heads, **mla_kwargs()),
                        "ln2": L.init_rmsnorm(cfg.d_model),
                        "mlp": L.init_mlp(km, cfg.d_model, cfg.d_ff_dense, True)}
            nd = cfg.first_dense_layers
            return {"dense_layers": self._stack(k2, nd, one_dense),
                    "layers": self._stack(k1, cfg.n_layers - nd, one_moe)}

        if kind == "vlm":
            per = cfg.cross_every
            n_units = cfg.n_layers // per
            n_self = per - 1

            def one_unit(k):
                ks, kc, km = jax.random.split(k, 3)

                def one_self(kk):
                    ka, km2 = jax.random.split(kk)
                    return {"ln1": L.init_rmsnorm(cfg.d_model),
                            "attn": A.init_attention(ka, cfg.d_model, cfg.n_heads,
                                                     cfg.n_kv_heads, cfg.head_dim),
                            "ln2": L.init_rmsnorm(cfg.d_model),
                            "mlp": L.init_mlp(km2, cfg.d_model, cfg.d_ff, True)}
                self_stack = self._stack(ks, n_self, one_self)
                cross = {"ln1": L.init_rmsnorm(cfg.d_model),
                         "attn": A.init_cross_attention(kc, cfg.d_model, cfg.n_heads,
                                                        cfg.n_kv_heads, cfg.head_dim),
                         "gate": jnp.zeros((1,), jnp.float32),
                         "ln2": L.init_rmsnorm(cfg.d_model),
                         "mlp": L.init_mlp(km, cfg.d_model, cfg.d_ff, True)}
                return {"self": self_stack, "cross": cross}
            return {"units": self._stack(k1, n_units, one_unit)}

        if kind == "xlstm":
            n_units = cfg.n_layers // 2

            def one_unit(k):
                km, ks = jax.random.split(k)
                return {"m_ln": L.init_rmsnorm(cfg.d_model),
                        "mlstm": S.init_mlstm(km, cfg.d_model, cfg.n_heads,
                                              conv_k=cfg.conv_kernel),
                        "s_ln": L.init_rmsnorm(cfg.d_model),
                        "slstm": S.init_slstm(ks, cfg.d_model, cfg.n_heads)}
            return {"units": self._stack(k1, n_units, one_unit)}

        if kind == "hymba":
            def one(k):
                ka, km, kf = jax.random.split(k, 3)
                return {"ln1": L.init_rmsnorm(cfg.d_model),
                        "attn": A.init_attention(ka, cfg.d_model, cfg.n_heads,
                                                 cfg.n_kv_heads, cfg.head_dim),
                        "mamba": S.init_mamba(km, cfg.d_model, cfg.d_inner,
                                              cfg.ssm_state, cfg.conv_kernel),
                        "mix_norm_a": L.init_rmsnorm(cfg.d_model),
                        "mix_norm_m": L.init_rmsnorm(cfg.d_model),
                        "ln2": L.init_rmsnorm(cfg.d_model),
                        "mlp": L.init_mlp(kf, cfg.d_model, cfg.d_ff, True)}
            return {"layers": self._stack(k1, cfg.n_layers, one)}

        raise ValueError(f"unknown block_kind {kind}")

    # --------------------------- flags ------------------------------------
    def _layer_flags(self) -> jnp.ndarray | None:
        """Per-layer is_global booleans for gemma/hymba patterns."""
        cfg = self.cfg
        if cfg.block_kind == "gemma":
            idx = jnp.arange(cfg.n_layers)
            return (idx % cfg.global_every) == (cfg.global_every - 1)
        if cfg.block_kind == "hymba":
            idx = jnp.arange(cfg.n_layers)
            return (idx == 0) | (idx == cfg.n_layers // 2) | (idx == cfg.n_layers - 1)
        return None

    # --------------------------- embed/unembed ----------------------------
    def _embed(self, params, tokens) -> jnp.ndarray:
        cfg = self.cfg
        if cfg.n_codebooks:
            tables = params["embed"]["table"].astype(cfg.dtype)  # (nq, V, d)
            return sum(tables[q][tokens[..., q]] for q in range(cfg.n_codebooks))
        return L.embed(params["embed"], tokens, cfg.dtype)

    def _unembed(self, params, x) -> jnp.ndarray:
        cfg = self.cfg
        head = params.get("lm_head", params["embed"])
        if cfg.n_codebooks:
            tables = head["table"].astype(x.dtype)  # (nq, V, d)
            return jnp.einsum("bsd,qvd->bsqv", x, tables)
        return L.unembed(head, x)

    # --------------------------- blocks ------------------------------------
    def _attn_block(self, p, x, *, positions, is_global=None, cache=None,
                    kv_len=None, mla: bool = False):
        cfg = self.cfg
        sh = self.sh
        h = L.rms_norm(p["ln1"], x)
        if mla:
            y, new_cache = A.mla_attention(
                p["attn"], h, n_heads=cfg.n_heads, kv_lora=cfg.kv_lora_rank,
                nope_dim=cfg.qk_nope_dim, rope_dim=cfg.qk_rope_dim,
                v_dim=cfg.v_head_dim, positions=positions,
                rope_theta=cfg.rope_theta, cache=cache, kv_len=kv_len,
                sharder=self.sh if self.mesh is not None else None)
        else:
            y, new_cache = A.attention(
                p["attn"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                head_dim=cfg.head_dim, positions=positions,
                rope_theta=cfg.rope_theta, window=cfg.window,
                is_global=is_global, qk_norm=cfg.qk_norm, cache=cache,
                kv_len=kv_len, cp_mesh=self._cp_mesh(), cp_dp=sh.dp,
                sharder=sh if self.mesh is not None else None)
        return sh.acts(x + y), new_cache

    def _cp_mesh(self):
        """Context-parallel mesh when head-TP is impossible (heads % tp)."""
        if self.mesh is None:
            return None
        if self.cfg.n_heads % self.mesh.shape[self.sh.tp] == 0:
            return None
        return self.mesh

    def _ffn_block(self, p, x):
        if "moe" in p:
            y, aux = self._moe(p["moe"], L.rms_norm(p["ln2"], x))
        else:
            y, aux = L.mlp(p["mlp"], L.rms_norm(p["ln2"], x),
                           gated=self.cfg.mlp_gated, act=self.cfg.mlp_act), 0.0
        return self.sh.acts(x + y), aux

    def _moe(self, p, x):
        cfg, sh = self.cfg, self.sh
        if self.mesh is None:
            return M.moe_ffn(p, x, top_k=cfg.top_k,
                             capacity_factor=cfg.capacity_factor)
        dp = sh.dp if x.shape[0] % sh.dp_size == 0 and x.shape[0] > 1 else None
        xspec = P(dp, None, None)
        wspec: dict = {"router": {"w": P(None, None)},
                       "up": P("model", None, "data"),
                       "gate": P("model", None, "data"),
                       "down": P("model", "data", None)}
        if "shared" in p:
            wspec["shared"] = {"up": {"w": P(None, "model")},
                               "gate": {"w": P(None, "model")},
                               "down": {"w": P("model", None)}}

        e_total = cfg.n_experts
        tp_size = self.mesh.shape["model"]

        def island(w, xx):
            # ZeRO gather of this layer's expert slice over 'data'; cast to
            # the compute dtype BEFORE the gather — halves the AG bytes
            # (§Perf iteration 4)
            w = dict(w)
            cd = xx.dtype
            w["up"] = jax.lax.all_gather(w["up"].astype(cd), "data", axis=2, tiled=True)
            w["gate"] = jax.lax.all_gather(w["gate"].astype(cd), "data", axis=2, tiled=True)
            w["down"] = jax.lax.all_gather(w["down"].astype(cd), "data", axis=1, tiled=True)
            off = jax.lax.axis_index("model") * (e_total // tp_size)
            y, aux = M.moe_ffn(w, xx, top_k=cfg.top_k,
                               capacity_factor=cfg.capacity_factor,
                               ep_axis="model", expert_offset=off,
                               n_experts_total=e_total)
            aux = jax.lax.pmean(aux, sh.dp) if dp is not None else aux
            return y, aux

        fn = jax.shard_map(island, mesh=self.mesh,
                           in_specs=(wspec, xspec),
                           out_specs=(xspec, P()))
        return fn(p, x)

    # --------------------------- forward (train/prefill) -------------------
    def forward(self, params, tokens, *, image_embeds=None, cache=None,
                kv_len=None, last_token_only: bool = False):
        """Returns (logits, aux_loss, new_cache). cache None => no caching
        (training). For prefill pass empty caches and kv_len=0;
        last_token_only skips the (B,S,V) logits transient (prefill only
        needs the final position)."""
        cfg = self.cfg
        sh = self.sh
        # SP residual only where its memory win matters (training): prefill
        # measured 24x more collective traffic under SP auto-resharding.
        sh.sp = cache is None
        x = self._embed(params, tokens)
        b, s = x.shape[:2]
        n_meta = 0
        if cfg.n_meta_tokens and cache is None or \
           (cfg.n_meta_tokens and kv_len is not None and isinstance(kv_len, int) and kv_len == 0):
            meta = jnp.broadcast_to(params["meta_tokens"].astype(x.dtype),
                                    (b, cfg.n_meta_tokens, x.shape[-1]))
            x = jnp.concatenate([meta, x], axis=1)
            n_meta = cfg.n_meta_tokens
            s = x.shape[1]
        x = sh.acts(x)
        positions = jnp.arange(s) if kv_len is None else kv_len + jnp.arange(s)
        flags = self._layer_flags()
        aux_total = 0.0

        kind = cfg.block_kind
        if kind in ("gqa", "gemma", "musicgen", "gqa_moe", "hymba"):
            x, aux_total, new_cache = self._run_flat_stack(
                params["layers"], x, positions, flags, cache, kv_len)
        elif kind == "mla_moe":
            dcache = cache["dense"] if cache is not None else None
            x, aux0, dnew = self._run_flat_stack(params["dense_layers"], x,
                                                 positions, None, dcache,
                                                 kv_len, mla=True)
            mcache = cache["moe"] if cache is not None else None
            x, aux1, mnew = self._run_flat_stack(params["layers"], x,
                                                 positions, None, mcache,
                                                 kv_len, mla=True)
            aux_total = aux0 + aux1
            new_cache = None if cache is None else {"dense": dnew, "moe": mnew}
        elif kind == "vlm":
            x, new_cache = self._run_vlm(params["units"], x, positions,
                                         image_embeds, cache, kv_len)
        elif kind == "xlstm":
            x, new_cache = self._run_xlstm(params["units"], x, cache)
        else:
            raise ValueError(kind)

        x = L.rms_norm(params["final_norm"], x)
        if n_meta:
            x = x[:, n_meta:]
        if last_token_only:
            x = x[:, -1:]
        logits = sh.logits(self._unembed(params, x))
        return logits, aux_total, new_cache

    # ------------------ flat homogeneous stacks (scan) ---------------------
    def _run_flat_stack(self, stack, x, positions, flags, cache, kv_len,
                        mla: bool = False):
        cfg = self.cfg
        is_hymba = cfg.block_kind == "hymba"

        def body(carry, inp):
            x = carry
            p = inp["p"]
            flag = inp.get("flag")
            c_in = inp.get("cache")
            if is_hymba:
                x, new_c, aux = self._hymba_layer(p, x, positions, flag, c_in, kv_len)
            else:
                x, new_c = self._attn_block(p, x, positions=positions,
                                            is_global=flag, cache=c_in,
                                            kv_len=kv_len, mla=mla)
                x, aux = self._ffn_block(p, x)
            return x, {"cache": new_c, "aux": aux}

        xs: dict[str, Any] = {"p": stack}
        if flags is not None:
            xs["flag"] = flags[:jax.tree.leaves(stack)[0].shape[0]]
        if cache is not None:
            xs["cache"] = cache

        body_fn = body
        if self.remat:
            body_fn = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
        x, ys = jax.lax.scan(body_fn, x, xs)
        aux = jnp.sum(ys["aux"]) if isinstance(ys["aux"], jnp.ndarray) else 0.0
        new_cache = ys["cache"] if cache is not None else None
        return x, aux, new_cache

    def _hymba_layer(self, p, x, positions, flag, cache, kv_len):
        cfg = self.cfg
        h = L.rms_norm(p["ln1"], x)
        a_cache = m_conv = m_ssm = None
        if cache is not None:
            a_cache = {"k": cache["k"], "v": cache["v"]}
            m_conv, m_ssm = cache["conv"], cache["ssm"]
        ya, new_a = A.attention(p["attn"], h, n_heads=cfg.n_heads,
                                n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
                                positions=positions, rope_theta=cfg.rope_theta,
                                window=cfg.window, is_global=flag,
                                cache=a_cache, kv_len=kv_len,
                                cp_mesh=self._cp_mesh(), cp_dp=self.sh.dp,
                                sharder=self.sh if self.mesh is not None else None)
        ym, (new_conv, new_ssm) = S.mamba_mix(
            p["mamba"], h, m_conv, m_ssm,
            sharder=self.sh if self.mesh is not None else None)
        # normalized fusion of the parallel heads (hymba mean-of-norms)
        y = 0.5 * (L.rms_norm(p["mix_norm_a"], ya) + L.rms_norm(p["mix_norm_m"], ym))
        x = self.sh.acts(x + y)
        x, aux = self._ffn_block(p, x)
        new_cache = None
        if cache is not None:
            new_cache = {"k": new_a["k"], "v": new_a["v"],
                         "conv": new_conv, "ssm": new_ssm}
        return x, new_cache, aux

    # ------------------------------ vlm ------------------------------------
    def _run_vlm(self, units, x, positions, image_embeds, cache, kv_len):
        cfg = self.cfg

        def unit_body(carry, inp):
            x = carry
            u = inp["p"]
            c_in = inp.get("cache")

            def self_body(xx, sinp):
                sp = sinp["p"]
                sc = sinp.get("cache")
                xx, new_c = self._attn_block(sp, xx, positions=positions,
                                             cache=sc, kv_len=kv_len)
                xx, _ = self._ffn_block(sp, xx)
                return xx, {"cache": new_c}

            sxs: dict[str, Any] = {"p": u["self"]}
            if c_in is not None:
                sxs["cache"] = c_in["self"]
            x, sys_ = jax.lax.scan(self_body, x, sxs)

            cp = u["cross"]
            h = L.rms_norm(cp["ln1"], x)
            y = A.cross_attention(cp["attn"], h, image_embeds,
                                  n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                                  head_dim=cfg.head_dim)
            x = self.sh.acts(x + jnp.tanh(cp["gate"]).astype(x.dtype) * y)
            y2 = L.mlp(cp["mlp"], L.rms_norm(cp["ln2"], x), gated=True)
            x = self.sh.acts(x + y2)
            new_c = {"self": sys_["cache"]} if c_in is not None else None
            return x, {"cache": new_c}

        xs: dict[str, Any] = {"p": units}
        if cache is not None:
            xs["cache"] = cache
        body = unit_body
        if self.remat:
            body = jax.checkpoint(unit_body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        x, ys = jax.lax.scan(body, x, xs)
        return x, (ys["cache"] if cache is not None else None)

    # ------------------------------ xlstm -----------------------------------
    def _run_xlstm(self, units, x, cache):
        cfg = self.cfg
        decode = cache is not None and x.shape[1] == 1

        def unit_body(carry, inp):
            x = carry
            u = inp["p"]
            c = inp.get("cache")
            if decode:
                ym, new_m = S.mlstm_decode(u["mlstm"], L.rms_norm(u["m_ln"], x),
                                           c["mlstm"], cfg.n_heads)
            elif c is not None:  # prefill: seed + hand back the state
                ym, new_m = S.mlstm_sequence(u["mlstm"], L.rms_norm(u["m_ln"], x),
                                             cfg.n_heads, state=c["mlstm"],
                                             return_state=True)
            else:
                ym = S.mlstm_sequence(u["mlstm"], L.rms_norm(u["m_ln"], x),
                                      cfg.n_heads)
                new_m = None
            x = x + ym
            ys_, new_s = S.slstm_sequence(u["slstm"], L.rms_norm(u["s_ln"], x),
                                          cfg.n_heads,
                                          state=(c["slstm"] if c is not None else None))
            x = self.sh.acts(x + ys_)
            new_c = {"mlstm": new_m, "slstm": new_s} if c is not None else None
            return x, {"cache": new_c}

        xs: dict[str, Any] = {"p": units}
        if cache is not None:
            xs["cache"] = cache
        body = unit_body
        if self.remat:
            body = jax.checkpoint(unit_body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        x, ys = jax.lax.scan(body, x, xs)
        return x, (ys["cache"] if cache is not None else None)

    # --------------------------- loss / steps ------------------------------
    def loss_fn(self, params, batch):
        tokens = batch["tokens"]
        logits, aux, _ = self.forward(params, tokens,
                                      image_embeds=batch.get("image_embeds"))
        logits = logits[:, :-1].astype(jnp.float32)
        targets = tokens[:, 1:]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        loss = nll.mean()
        return loss + 0.01 * aux, {"loss": loss, "aux": aux}

    # cache plumbing -------------------------------------------------------
    def _cache_layout(self, batch_size: int, max_len: int) -> Any:
        """Pytree of (shape, dtype, PartitionSpec, fill) cache descriptors."""
        cfg = self.cfg
        sh = self.sh
        dt = cfg.dtype
        total = max_len + cfg.n_meta_tokens

        def leaf(shape, dtype=dt, fill=0.0, **axkw):
            return (shape, dtype, sh.kv_cache_spec(shape, **axkw), fill)

        def rep(shape, dtype=jnp.float32, fill=0.0):
            # replicated-or-batch-sharded small state (recurrent states)
            spec = sh.kv_cache_spec(shape, batch_axis=1, seq_axis=1,
                                    head_axis=None)
            return (shape, dtype, spec, fill)

        def kv(n_layers):
            shape = (n_layers, batch_size, total, cfg.n_kv_heads, cfg.head_dim)
            return {"k": leaf(shape), "v": leaf(shape)}

        kind = cfg.block_kind
        if kind in ("gqa", "gemma", "musicgen", "gqa_moe"):
            return kv(cfg.n_layers)
        if kind == "mla_moe":
            def mla_cache(n):
                return {"c_kv": leaf((n, batch_size, total, cfg.kv_lora_rank),
                                     head_axis=None),
                        "k_rope": leaf((n, batch_size, total, cfg.qk_rope_dim),
                                       head_axis=None)}
            return {"dense": mla_cache(cfg.first_dense_layers),
                    "moe": mla_cache(cfg.n_layers - cfg.first_dense_layers)}
        if kind == "vlm":
            per = cfg.cross_every
            n_units = cfg.n_layers // per
            shape = (n_units, per - 1, batch_size, total, cfg.n_kv_heads,
                     cfg.head_dim)
            mk = lambda: leaf(shape, batch_axis=2, seq_axis=3, head_axis=4)
            return {"self": {"k": mk(), "v": mk()}}
        if kind == "xlstm":
            nu = cfg.n_layers // 2
            di = cfg.d_model * 2
            dh_m = di // cfg.n_heads
            dh_s = cfg.d_model // cfg.n_heads
            return {"mlstm": {"c": rep((nu, batch_size, cfg.n_heads, dh_m, dh_m)),
                              "n": rep((nu, batch_size, cfg.n_heads, dh_m)),
                              "m": rep((nu, batch_size, cfg.n_heads), fill=-1e30),
                              "conv": rep((nu, batch_size, cfg.conv_kernel - 1, di))},
                    "slstm": {"c": rep((nu, batch_size, cfg.n_heads, dh_s)),
                              "n": rep((nu, batch_size, cfg.n_heads, dh_s)),
                              "h": rep((nu, batch_size, cfg.n_heads, dh_s)),
                              "m": rep((nu, batch_size, cfg.n_heads, dh_s),
                                       fill=-1e30)}}
        if kind == "hymba":
            base = kv(cfg.n_layers)
            return {"k": base["k"], "v": base["v"],
                    "conv": rep((cfg.n_layers, batch_size,
                                 cfg.conv_kernel - 1, cfg.d_inner), dtype=dt),
                    "ssm": rep((cfg.n_layers, batch_size, cfg.d_inner,
                                cfg.ssm_state))}
        raise ValueError(kind)

    @staticmethod
    def _is_leaf(x):
        return isinstance(x, tuple) and len(x) == 4 and isinstance(x[0], tuple)

    def cache_specs(self, batch_size: int, max_len: int) -> Any:
        """PartitionSpec pytree for the cache (dryrun in_shardings)."""
        return jax.tree.map(lambda d: d[2],
                            self._cache_layout(batch_size, max_len),
                            is_leaf=self._is_leaf)

    def cache_shapes(self, batch_size: int, max_len: int) -> Any:
        return jax.tree.map(lambda d: jax.ShapeDtypeStruct(d[0], d[1]),
                            self._cache_layout(batch_size, max_len),
                            is_leaf=self._is_leaf)

    def init_cache(self, batch_size: int, max_len: int) -> Any:
        def make(d):
            shape, dtype, spec, fill = d
            x = jnp.full(shape, fill, dtype) if fill else jnp.zeros(shape, dtype)
            return self.sh(x, *spec) if self.mesh is not None else x
        return jax.tree.map(make, self._cache_layout(batch_size, max_len),
                            is_leaf=self._is_leaf)

    def prefill(self, params, tokens, cache, image_embeds=None):
        logits, _, cache = self.forward(params, tokens, cache=cache, kv_len=0,
                                        image_embeds=image_embeds,
                                        last_token_only=True)
        return logits, cache

    def decode_step(self, params, tokens, cache, pos, image_embeds=None):
        """One-token decode. pos: scalar current length (excl. meta)."""
        kv_len = pos + self.cfg.n_meta_tokens if self.cfg.n_meta_tokens else pos
        logits, _, cache = self.forward(params, tokens, cache=cache,
                                        kv_len=kv_len, image_embeds=image_embeds)
        return logits, cache
