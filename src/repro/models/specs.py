"""Parameter specifications: shapes + logical sharding axes per architecture.

The whole parameter tree of any assigned architecture is described *as data*
(``ParamSpec`` leaves), so `jax.eval_shape` is never needed for the dry-run:
shapes, shardings and parameter counts are all derived directly from specs.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig

Logical = Tuple[Optional[str], ...]


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    logical: Logical
    init: str = "normal"      # normal | zeros | ones | lru
    scale: float = 0.02

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def _norm_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    d = {"scale": ParamSpec((cfg.d_model,), ("embed",), "ones")}
    if cfg.norm == "layernorm":
        d["bias"] = ParamSpec((cfg.d_model,), ("embed",), "zeros")
    return d


def _attn_specs(cfg: ArchConfig, cross: bool = False) -> Dict[str, ParamSpec]:
    D, H, Hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.dh
    kvh = H if cross and cfg.encoder_decoder else Hkv
    s = {
        "wq": ParamSpec((D, H, dh), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((D, kvh, dh), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((D, kvh, dh), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((H, dh, D), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((H, dh), ("heads", "head_dim"), "zeros")
        s["bk"] = ParamSpec((kvh, dh), ("kv_heads", "head_dim"), "zeros")
        s["bv"] = ParamSpec((kvh, dh), ("kv_heads", "head_dim"), "zeros")
    return s


def _mlp_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    D, F = cfg.d_model, cfg.d_ff
    s = {"wi": ParamSpec((D, F), ("embed", "mlp")),
         "wo": ParamSpec((F, D), ("mlp", "embed"))}
    if cfg.act == "silu":
        s["wg"] = ParamSpec((D, F), ("embed", "mlp"))
    else:  # gelu with biases (whisper-style)
        s["bi"] = ParamSpec((F,), ("mlp",), "zeros")
        s["bo"] = ParamSpec((D,), ("embed",), "zeros")
    return s


def _moe_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    D, Fe, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": ParamSpec((D, E), ("embed", "expert")),
        "wi": ParamSpec((E, D, Fe), ("expert", "embed", "mlp")),
        "wg": ParamSpec((E, D, Fe), ("expert", "embed", "mlp")),
        "wo": ParamSpec((E, Fe, D), ("expert", "mlp", "embed")),
    }


def _rglru_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    D, R, CW = cfg.d_model, cfg.d_rnn or cfg.d_model, cfg.conv_width
    return {
        "wx": ParamSpec((D, R), ("embed", "rnn")),
        "wy": ParamSpec((D, R), ("embed", "rnn")),
        "conv_w": ParamSpec((CW, R), ("conv", "rnn")),
        "conv_b": ParamSpec((R,), ("rnn",), "zeros"),
        "lam": ParamSpec((R,), ("rnn",), "lru"),
        "wa": ParamSpec((R, R), ("rnn_in", "rnn")),
        "wi": ParamSpec((R, R), ("rnn_in", "rnn")),
        "wout": ParamSpec((R, D), ("rnn", "embed")),
    }


def _rwkv_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    D, F = cfg.d_model, cfg.d_ff
    s: Dict[str, ParamSpec] = {}
    for mu in ("mu_r", "mu_k", "mu_v", "mu_g", "mu_w"):
        s[mu] = ParamSpec((D,), ("embed",), "zeros")
    for w in ("wr", "wk", "wv", "wg"):
        s[w] = ParamSpec((D, D), ("embed", "rnn"))
    s["ww"] = ParamSpec((D, D), ("embed", "rnn"), scale=0.002)
    s["w_bias"] = ParamSpec((D,), ("rnn",), "lru")
    s["u"] = ParamSpec((D,), ("rnn",), "zeros")
    s["wo"] = ParamSpec((D, D), ("rnn", "embed"))
    s["gn_scale"] = ParamSpec((D,), ("rnn",), "ones")
    # channel mix
    s["c_mu_k"] = ParamSpec((D,), ("embed",), "zeros")
    s["c_mu_r"] = ParamSpec((D,), ("embed",), "zeros")
    s["c_wk"] = ParamSpec((D, F), ("embed", "mlp"))
    s["c_wv"] = ParamSpec((F, D), ("mlp", "embed"))
    s["c_wr"] = ParamSpec((D, D), ("embed", "rnn"))
    return s


def block_specs(cfg: ArchConfig, kind: str) -> Dict:
    """Specs of one transformer block of the given kind."""
    if kind in ("attn", "local"):
        return {"ln1": _norm_specs(cfg), "attn": _attn_specs(cfg),
                "ln2": _norm_specs(cfg), "mlp": _mlp_specs(cfg)}
    if kind == "moe":
        return {"ln1": _norm_specs(cfg), "attn": _attn_specs(cfg),
                "ln2": _norm_specs(cfg), "moe": _moe_specs(cfg)}
    if kind == "cross":
        return {"ln1": _norm_specs(cfg), "xattn": _attn_specs(cfg, cross=True),
                "gate": ParamSpec((1,), (None,), "zeros"),
                "ln2": _norm_specs(cfg), "mlp": _mlp_specs(cfg)}
    if kind == "rglru":
        return {"ln1": _norm_specs(cfg), "rec": _rglru_specs(cfg),
                "ln2": _norm_specs(cfg), "mlp": _mlp_specs(cfg)}
    if kind == "rwkv":
        return {"ln1": _norm_specs(cfg), "ln2": _norm_specs(cfg),
                "mix": _rwkv_specs(cfg)}
    if kind == "enc":
        return {"ln1": _norm_specs(cfg), "attn": _attn_specs(cfg),
                "ln2": _norm_specs(cfg), "mlp": _mlp_specs(cfg)}
    if kind == "dec":
        return {"ln1": _norm_specs(cfg), "attn": _attn_specs(cfg),
                "lnx": _norm_specs(cfg), "xattn": _attn_specs(cfg, cross=True),
                "ln2": _norm_specs(cfg), "mlp": _mlp_specs(cfg)}
    raise ValueError(kind)


def _stack(tree, n: int):
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.logical,
                            s.init, s.scale), tree,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def model_specs(cfg: ArchConfig) -> Dict:
    """Full parameter tree spec for an architecture."""
    D, V = cfg.d_model, cfg.vocab
    specs: Dict = {
        "embed": {"tok": ParamSpec((V, D), ("vocab", "embed"))},
        "final_norm": _norm_specs(cfg),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = {"w": ParamSpec((D, V), ("embed", "vocab"))}
    pat = cfg.pattern
    if cfg.n_groups > 0:
        specs["groups"] = {f"b{i}_{k}": _stack(block_specs(cfg, k),
                                               cfg.n_groups)
                           for i, k in enumerate(pat)}
    if cfg.n_rem_layers:
        specs["rem"] = {f"r{i}_{k}": block_specs(cfg, k)
                        for i, k in enumerate(pat[: cfg.n_rem_layers])}
    if cfg.family == "vlm":
        specs["img_proj"] = {"w": ParamSpec((D, D), ("embed", "embed_out"))}
    if cfg.encoder_decoder:
        ne = cfg.n_encoder_layers
        specs["encoder"] = {
            "groups": {"b0_enc": _stack(block_specs(cfg, "enc"), ne)},
            "final_norm": _norm_specs(cfg),
            "in_proj": {"w": ParamSpec((D, D), ("embed", "embed_out"))},
        }
    return specs


# ---------------------------------------------------------------------------
def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def count_params(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=is_spec)
    return int(sum(int(np.prod(s.shape)) for s in leaves))


def expert_params(cfg: ArchConfig) -> Tuple[int, int]:
    """(total expert params over all moe layers, per-expert-per-layer)."""
    per = 3 * cfg.d_model * cfg.d_ff
    n_moe = sum(1 for k in cfg.layer_kinds() if k == "moe")
    return per * cfg.n_experts * n_moe, per


def spec_shapes(specs) -> Dict:
    """ShapeDtypeStructs (fp32 params) matching the spec tree."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), specs,
        is_leaf=is_spec)


def logical_axes(specs) -> Dict:
    return jax.tree.map(lambda s: s.logical, specs, is_leaf=is_spec)


def _init_leaf(s: ParamSpec, key) -> jnp.ndarray:
    if s.init == "zeros":
        return jnp.zeros(s.shape, jnp.float32)
    if s.init == "ones":
        return jnp.ones(s.shape, jnp.float32)
    if s.init == "lru":
        # Λ such that RG-LRU decay starts in ~[0.9, 0.999]
        u = jax.random.uniform(key, s.shape, jnp.float32, -8.0, -4.0)
        return u
    return jax.random.normal(key, s.shape, jnp.float32) * s.scale


def init_params(specs, key) -> Dict:
    """Deterministic init: every leaf gets a key derived from its path."""
    flat, treedef = jax.tree.flatten_with_path(specs, is_leaf=is_spec)
    leaves = []
    for path, s in flat:
        name = "/".join(str(p) for p in path)
        h = int.from_bytes(hashlib.sha256(name.encode()).digest()[:4], "big")
        leaves.append(_init_leaf(s, jax.random.fold_in(key, h)))
    return jax.tree.unflatten(treedef, leaves)
