"""Sharding policy: map parameter/cache/batch pytrees to PartitionSpecs.

Policy (MaxText-style 2D "FSDP + tensor parallel"):
  * mesh axes: ('pod', 'data', 'model') multi-pod, ('data', 'model') single.
  * weights: the tensor-parallel dim (heads / d_ff / experts) shards on
    'model'; when ``cfg.fsdp_weights`` the other big dim shards on
    'data' (ZeRO-3 via GSPMD — all-gathered at use). Weights are never
    sharded on 'pod' (pure data parallel across pods).
  * activations: batch shards on ('pod', 'data').
  * decode caches: batch on ('pod','data'); for long_500k (batch=1) the
    *sequence* dim of KV/latent buffers shards on ('pod','data') instead.
  * any dim not divisible by its mesh axis falls back to replication.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

NORM_PARENTS = {"norm1", "norm2", "norm_c", "norm_h", "norm_e", "final_norm",
                "q_norm", "kv_norm", "ln_x"}
RWKV_SMALL = {"w0", "mu", "u", "w_lora_a", "w_lora_b"}
COL_PARENTS = {"wq", "wk", "wv", "gate", "up", "wq_b", "wk_b", "wv_b", "in_proj"}
ROW_PARENTS = {"wo", "down", "out_proj"}


def _path_names(path):
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return out


def axis_sizes(mesh):
    sizes = getattr(mesh, "axis_sizes", None)   # AbstractMesh
    if sizes is not None:
        return dict(zip(mesh.axis_names, sizes))
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def abstract_mesh(shape, axes):
    """Device-free mesh for spec construction/testing."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes))


class Policy:
    """``tuned=False`` is the naive paper-faithful baseline recorded in
    EXPERIMENTS.md §Roofline; ``tuned=True`` applies the §Perf hillclimb
    changes:
      * head-aware attention sharding: wq/wk/wv outputs shard on 'model'
        only when the *head count* divides the axis (a flat-divisible
        but head-splitting sharding makes GSPMD insert all-reduces
        inside the attention chunk loops);
      * 2D expert sharding: MoE expert dim shards over ('data','model')
        when E divides data*model (deepseek: 256 experts over 256 chips)
        — removes the FSDP gather of expert weights entirely.
    """

    def __init__(self, cfg, mesh, *, tuned: bool = False, strategy: str = "2d"):
        """strategy='2d': batch on ('pod','data'), tensor-parallel on
        'model' (+ ZeRO-3 on 'data' when cfg.fsdp_weights) — the
        baseline Megatron-style mapping.

        strategy='fsdp': batch on ('pod','data','model') and ALL weights
        ZeRO-3-sharded across both intra-pod axes — no tensor
        parallelism, so the per-layer Megatron activation all-reduces
        disappear entirely; weights are all-gathered per layer instead.
        §Perf iteration 2: wins whenever per-layer weight bytes <
        per-layer activation bytes x TP traffic (true for train_4k on
        every dense arch here). MoE experts keep the expert-parallel
        dimension (gathering full expert stacks would blow HBM)."""
        if strategy not in ("2d", "fsdp"):
            raise ValueError(strategy)
        self.cfg = cfg
        self.mesh = mesh
        self.tuned = tuned
        self.strategy = strategy
        self.sizes = axis_sizes(mesh)
        if strategy == "fsdp":
            self.dp = tuple(a for a in ("pod", "data", "model")
                            if a in self.sizes)
        else:
            self.dp = tuple(a for a in ("pod", "data") if a in self.sizes)
        self.fsdp = "data" if (cfg is not None and getattr(cfg, "fsdp_weights", False)
                               and "data" in self.sizes) else None
        model = self.sizes.get("model", 1)
        self.heads_ok = cfg is None or cfg.n_heads % model == 0
        self.kv_ok = cfg is None or cfg.n_kv_heads % model == 0
        dm = model * self.sizes.get("data", 1)
        self.experts_2d = (cfg is not None and cfg.n_experts
                           and cfg.n_experts % dm == 0)

    def dp_size(self):
        n = 1
        for a in self.dp:
            n *= self.sizes[a]
        return n

    def _fit(self, spec, shape):
        """Replace axes that don't divide their dim with None."""
        out = []
        for dim, ax in zip(shape, spec):
            if ax is None:
                out.append(None)
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                n *= self.sizes.get(a, 1)
            out.append(ax if dim % n == 0 else None)
        return P(*out)

    # ------------------------------------------------------------ params

    def _base_param_spec(self, names, shape):
        last = names[-1]
        parent = names[-2] if len(names) > 1 else ""
        F = self.fsdp
        nd = len(shape)
        if parent in NORM_PARENTS or last in RWKV_SMALL or last == "gate_attn":
            return (None,) * nd
        if last == "emb":
            # vocab-sharded: GSPMD partitions the gather as masked
            # local-lookup + all-reduce (d-sharded tables break the
            # partitioner's gather path under grad).
            return (None, "model", None)
        if parent == "lm_head":
            return (None, "model") if last == "w" else ("model",)
        if parent in ("cond_proj",):
            return (None,) * nd
        if parent == "mlp" and nd == 3 and last in ("gate", "up"):
            if self.tuned and self.experts_2d:
                return (("data", "model"), None, None)
            return ("model", None, F)          # MoE experts
        if parent == "mlp" and nd == 3 and last == "down":
            if self.tuned and self.experts_2d:
                return (("data", "model"), None, None)
            return ("model", F, None)
        if last == "router":
            return (None, None)
        if last == "conv_w":
            return (None, "model")
        if last in ("conv_b", "dt_bias", "D"):
            return ("model",)
        if last == "A_log":
            return ("model", None)
        if parent == "x_proj":
            return ("model", None) if last == "w" else (None,)
        if parent == "dt_w":
            return (None, "model") if last == "w" else ("model",)
        if parent in COL_PARENTS:
            if self.tuned and parent in ("wk", "wv") and not self.kv_ok:
                return (F, None) if last == "w" else (None,)
            if self.tuned and parent == "wq" and not self.heads_ok:
                return (F, None) if last == "w" else (None,)
            return (F, "model") if last == "w" else ("model",)
        if parent in ROW_PARENTS:
            if self.tuned and parent == "wo" and not self.heads_ok:
                return (None, F) if last == "w" else (None,)
            return ("model", F) if last == "w" else (None,)
        if parent in ("wq_a", "wkv_a"):
            return (F, None) if last == "w" else (None,)
        if parent == "proj":                   # MTP projection
            return (F, None) if last == "w" else (None,)
        if last in ("wr", "wk", "wv", "wg") and nd == 2:   # rwkv matrices
            return (None, "model")
        if last == "wo" and nd == 2:
            return ("model", None)
        return (None,) * nd

    def param_spec(self, path, leaf):
        names = _path_names(path)
        shape = leaf.shape
        stacked = 1 if names and names[0] == "groups" else 0
        base = self._base_param_spec(names, shape[stacked:])
        if self.strategy == "fsdp":
            base = self._to_fsdp(names, base, shape[stacked:])
        spec = (None,) * stacked + tuple(base)
        return self._fit(spec, shape)

    def _to_fsdp(self, names, base, shape):
        """Rewrite a 2D spec for the pure-FSDP strategy: the former
        tensor-parallel ('model') placement is dropped and the largest
        dim is ZeRO-3-sharded over ('data','model'). MoE expert stacks
        keep the expert dim sharded (never gathered whole)."""
        last = names[-1]
        parent = names[-2] if len(names) > 1 else ""
        both = ("data", "model")
        if parent == "mlp" and len(shape) == 3 and last in ("gate", "up", "down"):
            if self.experts_2d:
                return (both, None, None)
            return ("model", "data" if shape[1] % self.sizes.get("data", 1) == 0
                    else None, None) if last != "down" else ("model", "data", None)
        if len(shape) < 2 or all(a is None for a in base):
            return tuple(None for _ in shape)
        # shard the largest dim over both axes
        big = max(range(len(shape)), key=lambda i: shape[i])
        return tuple(both if i == big else None for i in range(len(shape)))

    def param_pspecs(self, params):
        return jax.tree_util.tree_map_with_path(self.param_spec, params)

    # ------------------------------------------------------------ caches

    def cache_spec(self, path, leaf, *, long=False):
        names = _path_names(path)
        last = names[-1]
        shape = leaf.shape
        nd = len(shape)
        dp = self.dp
        # every cache leaf is stacked: (count, B, ...) except 'pos' (count, W)
        if last == "pos":
            return P(*(None,) * nd)
        if last in ("k", "v") and nd == 5:          # (count,B,W,KV,D) self-attn
            seq_ax = dp if long else None
            return self._fit((None, None if long else dp, seq_ax, "model", None), shape)
        if last in ("k", "v") and nd == 5:
            pass
        if last in ("ckv", "krope"):                # (count,B,W,r)
            seq_ax = dp if long else None
            return self._fit((None, None if long else dp, seq_ax, None), shape)
        if "cross" in names or "cond" in names:     # (count,B,Cs,KV,D)
            return self._fit((None, None if long else dp, None, "model", None), shape)
        if "ssm" in names and nd == 4 and shape[-1] != shape[-2]:
            # conv state (count,B,dc-1,di) or h (count,B,di,st)
            if shape[-2] > shape[-1]:
                return self._fit((None, None if long else dp, "model", None), shape)
            return self._fit((None, None if long else dp, None, "model"), shape)
        if last == "state" and nd == 5:             # rwkv (count,B,H,hd,hd)
            return self._fit((None, None if long else dp, "model", None, None), shape)
        if nd >= 2:
            return self._fit((None, None if long else dp) + (None,) * (nd - 2), shape)
        return P(*(None,) * nd)

    def cache_pspecs(self, cache, *, long=False):
        return jax.tree_util.tree_map_with_path(
            lambda p, l: self.cache_spec(p, l, long=long), cache)

    # ------------------------------------------------------------- misc

    def batch_spec(self, leaf):
        return self._fit((self.dp,) + (None,) * (leaf.ndim - 1), leaf.shape)

    def batch_pspecs(self, batch):
        return jax.tree.map(self.batch_spec, batch)

    def opt_pspecs(self, params, opt_state):
        """Optimizer state mirrors param sharding (factored dims inherit)."""
        pspecs = self.param_pspecs(params)

        def match(path, leaf):
            names = _path_names(path)
            if names and names[-1] in ("step",):
                return P()
            # walk to the corresponding param spec by stripping m/v/vr/vc keys
            stripped = [n for n in names if n not in ("m", "v", "vr", "vc")]
            sub = pspecs
            for n in stripped:
                if isinstance(sub, dict) and n in sub:
                    sub = sub[n]
                elif isinstance(sub, (list, tuple)):
                    sub = sub[int(n)]
                else:
                    return P(*(None,) * leaf.ndim)
            if not isinstance(sub, P):
                return P(*(None,) * leaf.ndim)
            spec = tuple(sub)
            if names[-1] == "vr":       # param shape minus last dim
                spec = spec[:-1]
            elif names[-1] == "vc":     # param shape minus second-to-last
                spec = spec[:-2] + spec[-1:]
            spec = spec + (None,) * (leaf.ndim - len(spec))
            return self._fit(spec[:leaf.ndim], leaf.shape)

        return jax.tree_util.tree_map_with_path(match, opt_state)

    def shardings(self, pspecs):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), pspecs,
                            is_leaf=lambda x: isinstance(x, P))

    def constrain(self, x):
        """Activation constraint for (B, S, d) hiddens."""
        if x.ndim == 3:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, P(self.dp, None, None)))
        return x


def abstract_params(cfg, init_fn):
    """Shape-only params via eval_shape (no allocation)."""
    return jax.eval_shape(lambda k: init_fn(cfg, k), jax.random.PRNGKey(0))
