"""Decoder LM covering every assigned family (dense / moe / ssm / hybrid /
vlm / audio) with a layer-granular API.

Parameters are stored with blocks STACKED on a leading [L, ...] axis:
  * full-model paths (train/prefill/decode) run ``lax.scan`` over the
    stack — one compiled block body regardless of depth (fast compiles,
    exactly what the multi-pod dry-run lowers);
  * the Oobleck pipeline runtime slices ``blocks[u:v]`` per stage — layer
    granularity is the paper's unit of planning, state copy and sync.

VLM/audio frontends are STUBS per the task spec: ``forward`` accepts
precomputed frontend embeddings which are concatenated ahead of the token
embeddings; the loss masks those positions out.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (cross_entropy, embed, fused_cross_entropy,
                                 init_embedding, init_mlp, init_rms_norm,
                                 mlp, unembed)

Constrain = Callable[[jax.Array, str], jax.Array]


def _identity_constrain(x: jax.Array, name: str) -> jax.Array:
    return x


@dataclasses.dataclass
class Model:
    arch: ArchConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    remat: bool = True
    # remat policy: "full" recomputes everything (min memory);
    # "dots" saves matmul outputs (jax dots_with_no_batch_dims_saveable —
    # trades ~1.3x HBM for skipping GEMM recompute in backward).
    remat_policy: str = "full"
    # "kernel" routes stage layers through the Pallas kernels in
    # kernels/ops.py (fwd AND bwd custom_vjp, autotuned blocks); "auto"
    # resolves to "kernel" on an accelerator (where ops.kernel_lowers
    # raises if the kernel does not compile, DESIGN.md §13) and to the
    # pure-XLA path on the CPU.
    attn_impl: str = "blocked"          # blocked | naive | kernel | auto
    ssd_impl: str = "chunked"           # chunked | scan | kernel | auto
    moe_impl: str = "dense"             # dense | grouped
    # "fused" routes the residual-add+RMSNorm block epilogue and the
    # QKV projection through ops.fused_add_rmsnorm / ops.fused_qkv
    # (Pallas on an accelerator, XLA-level fusion on the CPU); "none"
    # keeps the op-per-line formulation.  "auto" == "fused".
    fuse: str = "auto"                  # auto | fused | none
    constrain: Constrain = _identity_constrain
    # hook applied to a block's params at entry (FSDP gather-at-use)
    unshard: Callable[[Dict], Dict] = lambda tree: tree
    scan_layers: bool = True
    # > 0: compute the training loss with the chunked fused CE (never
    # materializes [B, S, V] logits) — required at production scale.
    loss_chunk: int = 0
    # unroll the layer scan: the dry-run sets this so cost_analysis sees
    # every layer (XLA counts while-loop bodies once) — roofline fidelity.
    scan_unroll: bool = False

    def __post_init__(self):
        if "auto" in (self.attn_impl, self.ssd_impl):
            from repro.kernels import ops as kops
            if self.attn_impl == "auto":
                ok = (kops.kernel_lowers("flash_fwd")
                      and kops.kernel_lowers("flash_bwd"))
                self.attn_impl = "kernel" if ok else "blocked"
            if self.ssd_impl == "auto":
                ok = (kops.kernel_lowers("ssd_fwd")
                      and kops.kernel_lowers("ssd_bwd"))
                self.ssd_impl = "kernel" if ok else "chunked"
        if self.fuse == "auto":
            self.fuse = "fused"

    # ------------------------------------------------------------------
    # Init
    # ------------------------------------------------------------------
    def init(self, rng: jax.Array) -> Dict:
        a, pd = self.arch, self.param_dtype
        k_emb, k_blocks, k_head = jax.random.split(rng, 3)
        block_keys = jax.random.split(k_blocks, a.num_layers)
        blocks = [self._init_block(k) for k in block_keys]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
        params = {
            "embed": init_embedding(k_emb, a.vocab_size, a.d_model, pd),
            "blocks": stacked,
            "final_norm": init_rms_norm(a.d_model, pd),
        }
        if not a.tie_embeddings:
            params["head"] = init_embedding(k_head, a.vocab_size, a.d_model, pd)
        return params

    def _init_block(self, rng) -> Dict:
        a, pd = self.arch, self.param_dtype
        ks = jax.random.split(rng, 4)
        p: Dict = {"ln1": init_rms_norm(a.d_model, pd)}
        if a.family == "ssm":
            p["mamba"] = ssm_lib.init_mamba(ks[0], a, pd)
            return p
        if a.hybrid_parallel_heads:
            p["attn"] = attn_lib.init_attention(ks[0], a, pd)
            p["mamba"] = ssm_lib.init_mamba(ks[1], a, pd)
        else:
            p["attn"] = attn_lib.init_attention(ks[0], a, pd)
        p["ln2"] = init_rms_norm(a.d_model, pd)
        if a.moe is not None:
            p["moe"] = moe_lib.init_moe(ks[2], a, pd)
        elif a.d_ff:
            p["mlp"] = init_mlp(ks[3], a.d_model, a.d_ff, a.mlp_variant, pd)
        return p

    # ------------------------------------------------------------------
    # Single block (the pipeline runtime's unit)
    # ------------------------------------------------------------------
    def block(self, bp: Dict, x: jax.Array, aux: jax.Array) -> Tuple[jax.Array, jax.Array]:
        a = self.arch
        bp = self.unshard(bp)
        h = self._norm(bp["ln1"], x)
        if a.family == "ssm":
            x = x + ssm_lib.mamba(bp["mamba"], a, h, evaluator=self.ssd_impl)
            return self.constrain(x, "act"), aux
        fused = self.fuse == "fused"
        if a.hybrid_parallel_heads:
            branch = 0.5 * (attn_lib.attention(bp["attn"], a, h,
                                               impl=self.attn_impl,
                                               fused=fused)
                            + ssm_lib.mamba(bp["mamba"], a, h,
                                            evaluator=self.ssd_impl))
        else:
            branch = attn_lib.attention(bp["attn"], a, h,
                                        impl=self.attn_impl, fused=fused)
        if fused:
            # one pass over the residual: (x + branch) and its RMSNorm
            # come out of a single fused epilogue (ops.fused_add_rmsnorm)
            from repro.kernels import ops as kops
            x, h = kops.fused_add_rmsnorm(x, branch,
                                          bp["ln2"].astype(x.dtype),
                                          eps=a.rms_norm_eps)
            x = self.constrain(x, "act")
        else:
            x = x + branch
            x = self.constrain(x, "act")
            h = self._norm(bp["ln2"], x)
        if a.moe is not None:
            y, a_loss = self._moe(bp["moe"], h)
            x = x + y
            aux = aux + a_loss
        elif a.d_ff:
            x = x + mlp(bp["mlp"], h, a.mlp_variant)
        return self.constrain(x, "act"), aux

    def _moe(self, p, h):
        import functools
        fns = {"dense": moe_lib.moe_mlp, "grouped": moe_lib.moe_mlp_grouped,
               "capacity": moe_lib.moe_mlp_capacity,
               "capacity_vec": functools.partial(moe_lib.moe_mlp_capacity,
                                                 scan_groups=False)}
        return fns[self.moe_impl](p, self.arch, h)

    def _norm(self, w, x):
        from repro.models.layers import rms_norm
        return rms_norm(w.astype(x.dtype), x, self.arch.rms_norm_eps)

    def run_blocks(self, blocks: Dict, x: jax.Array,
                   aux: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Apply a stacked slice of blocks (full model or one stage)."""
        body = self.block
        if self.remat:
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if self.remat_policy == "dots" else None)
            body = jax.checkpoint(body, policy=policy)
        if self.scan_layers:
            def step(carry, bp):
                x, aux = carry
                x, aux = body(bp, x, aux)
                return (x, aux), None
            n = jax.tree.leaves(blocks)[0].shape[0]
            (x, aux), _ = jax.lax.scan(step, (x, aux), blocks,
                                       unroll=n if self.scan_unroll else 1)
        else:
            n = jax.tree.leaves(blocks)[0].shape[0]
            for i in range(n):
                bp = jax.tree.map(lambda t: t[i], blocks)
                x, aux = body(bp, x, aux)
        return x, aux

    # ------------------------------------------------------------------
    # Full forward / loss
    # ------------------------------------------------------------------
    def forward(self, params: Dict, tokens: jax.Array,
                frontend_embeds: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
        """tokens: [b, S_text] -> logits [b, S, V], aux loss."""
        x, aux = self.hidden_states(params, tokens, frontend_embeds)
        head = params.get("head", params["embed"])
        logits = unembed(head, x)
        return self.constrain(logits, "logits"), aux

    def loss(self, params: Dict, batch: Dict) -> Tuple[jax.Array, Dict]:
        # labels are PRE-SHIFTED next-token targets (labels[:, t] is the
        # target for position t) — the data pipeline emits arr[:, 1:].
        # The final position is excluded from the mean: keeping the
        # reduction at S-1 positions preserves bit-exact compiled/eager
        # parity (test_executor.py) across the labels-convention change.
        labels = batch["labels"]
        coef = (self.arch.moe.router_aux_loss_coef
                if self.arch.moe is not None else 0.0)
        if self.loss_chunk:
            x, aux = self.hidden_states(params, batch["tokens"],
                                        batch.get("frontend_embeds"))
            ft = x.shape[1] - labels.shape[1]
            if ft:
                x = x[:, ft:]
            head = params.get("head", params["embed"])
            nll = fused_cross_entropy(x, head["table"], labels,
                                      self.loss_chunk,
                                      batch.get("mask", None))
        else:
            logits, aux = self.forward(params, batch["tokens"],
                                       batch.get("frontend_embeds"))
            ft = logits.shape[1] - labels.shape[1]
            if ft:
                logits = logits[:, ft:]
            mask = batch.get("mask", None)
            nll = cross_entropy(logits[:, :-1], labels[:, :-1],
                                mask[:, :-1] if mask is not None else None)
        total = nll + coef * aux
        return total, {"nll": nll, "aux": aux}

    def hidden_states(self, params: Dict, tokens: jax.Array,
                      frontend_embeds: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, jax.Array]:
        """Forward up to (and including) the final norm; no head."""
        x = embed(params["embed"], tokens, self.dtype)
        if frontend_embeds is not None:
            x = jnp.concatenate([frontend_embeds.astype(self.dtype), x], axis=1)
        x = self.constrain(x, "act")
        aux = jnp.zeros((), jnp.float32)
        x, aux = self.run_blocks(params["blocks"], x, aux)
        return self._norm(params["final_norm"], x), aux

    # ------------------------------------------------------------------
    # Serving: prefill + single-token decode with per-layer caches
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> Dict:
        a = self.arch
        caches = []
        for _ in range(a.num_layers):
            c: Dict = {}
            if a.family == "ssm" or a.hybrid_parallel_heads:
                c["mamba"] = ssm_lib.init_mamba_cache(a, batch, self.dtype)
            if a.num_heads:
                c["attn"] = attn_lib.init_kv_cache(a, batch, max_len, self.dtype)
            caches.append(c)
        return jax.tree.map(lambda *xs: jnp.stack(xs), *caches)

    def decode_block(self, bp: Dict, cache: Dict, x: jax.Array,
                     pos: jax.Array) -> Tuple[jax.Array, Dict]:
        a = self.arch
        bp = self.unshard(bp)
        h = self._norm(bp["ln1"], x)
        new_cache: Dict = {}
        if a.family == "ssm":
            y, new_cache["mamba"] = ssm_lib.mamba_decode(bp["mamba"], a, h,
                                                         cache["mamba"])
            return x + y, new_cache
        if a.hybrid_parallel_heads:
            ya, new_cache["attn"] = attn_lib.decode_attention(
                bp["attn"], a, h, cache["attn"], pos,
                constrain=self.constrain)
            ym, new_cache["mamba"] = ssm_lib.mamba_decode(bp["mamba"], a, h,
                                                          cache["mamba"])
            x = x + 0.5 * (ya + ym)
        else:
            ya, new_cache["attn"] = attn_lib.decode_attention(
                bp["attn"], a, h, cache["attn"], pos,
                constrain=self.constrain)
            x = x + ya
        h = self._norm(bp["ln2"], x)
        if a.moe is not None:
            y, _ = self._moe(bp["moe"], h)
            x = x + y
        elif a.d_ff:
            x = x + mlp(bp["mlp"], h, a.mlp_variant)
        return self.constrain(x, "act"), new_cache

    def decode_step(self, params: Dict, token: jax.Array, cache: Dict,
                    pos: jax.Array) -> Tuple[jax.Array, Dict]:
        """token: [b, 1] int32; pos: scalar int32 current position, or
        [b] int32 per-example positions (serving slot caches decode each
        row at its own offset).  Returns (logits [b, 1, V], new stacked
        cache)."""
        x = embed(params["embed"], token, self.dtype)
        x = self.constrain(x, "act")

        def step(x, inp):
            bp, c = inp
            x, c_new = self.decode_block(bp, c, x, pos)
            return x, c_new

        x, new_cache = jax.lax.scan(step, x, (params["blocks"], cache))
        x = self._norm(params["final_norm"], x)
        head = params.get("head", params["embed"])
        logits = unembed(head, x)
        return self.constrain(logits, "logits"), new_cache

    def prefill(self, params: Dict, tokens: jax.Array,
                frontend_embeds: Optional[jax.Array] = None) -> jax.Array:
        """Prefill = forward producing LAST-position logits only: the
        hidden states are sliced before the head projection, so the
        [B, S, V] logits tensor is never built (the KV-cache fill is the
        attention computation itself)."""
        x, _ = self.hidden_states(params, tokens, frontend_embeds)
        head = params.get("head", params["embed"])
        logits = unembed(head, x[:, -1:])
        return self.constrain(logits, "logits")
