"""Architecture registry: --arch <id> -> ModelBundle with a uniform interface.

Bundle methods (all pure, jit/vmap-able):
    init(key) -> params
    loss(params, batch) -> scalar             (train_step inner)
    prefill(params, batch, caches) -> (logits, caches)
    decode(params, caches, batch) -> (logits, caches)
    init_caches(batch, max_len, n_chunks) -> caches
    make_batch(kind, B, S, key) -> concrete batch    (smoke tests / examples)
    batch_specs(kind, B, S) -> dict of ShapeDtypeStruct (abstract inputs)
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from .config import ArchConfig

ARCH_IDS = [
    "dbrx-132b", "qwen3-moe-235b-a22b", "zamba2-1.2b", "h2o-danube-3-4b",
    "phi3-medium-14b", "phi4-mini-3.8b", "internlm2-20b", "rwkv6-3b",
    "qwen2-vl-7b", "whisper-small",
]

_CONFIG_MODULES = {
    "dbrx-132b": "repro.configs.dbrx_132b",
    "qwen3-moe-235b-a22b": "repro.configs.qwen3_moe_235b_a22b",
    "zamba2-1.2b": "repro.configs.zamba2_1p2b",
    "h2o-danube-3-4b": "repro.configs.h2o_danube3_4b",
    "phi3-medium-14b": "repro.configs.phi3_medium_14b",
    "phi4-mini-3.8b": "repro.configs.phi4_mini_3p8b",
    "internlm2-20b": "repro.configs.internlm2_20b",
    "rwkv6-3b": "repro.configs.rwkv6_3b",
    # not in ARCH_IDS: the benchmark's Finch cut (bench/configs)
    "rwkv6-1.6b": "repro.configs.rwkv6_1b6",
    "qwen2-vl-7b": "repro.configs.qwen2_vl_7b",
    "whisper-small": "repro.configs.whisper_small",
}

_FAMILY_MODULES = {
    "dense": "repro.models.transformer",
    "vlm": "repro.models.transformer",
    "moe": "repro.models.moe",
    "hybrid": "repro.models.hybrid",
    "ssm": "repro.models.rwkv6",
    "audio": "repro.models.encdec",
}


def get_config(arch_id: str) -> ArchConfig:
    return importlib.import_module(_CONFIG_MODULES[arch_id]).CONFIG


@dataclass
class ModelBundle:
    cfg: ArchConfig

    def __post_init__(self):
        self.mod = importlib.import_module(_FAMILY_MODULES[self.cfg.family])

    # -- core fns ----------------------------------------------------------
    def init(self, key):
        return self.mod.init(key, self.cfg)

    def loss(self, params, batch):
        return self.mod.loss(params, batch, cfg=self.cfg)

    def prefill(self, params, batch, caches):
        return self.mod.prefill(params, batch, caches, cfg=self.cfg)

    def decode(self, params, caches, batch):
        return self.mod.decode_step(params, caches, batch, cfg=self.cfg)

    def init_caches(self, batch: int, max_len: int, n_chunks: int = 16,
                    dtype=jnp.bfloat16):
        return self.mod.init_caches(self.cfg, batch, max_len, n_chunks, dtype)

    # -- batch construction --------------------------------------------------
    def _token_specs(self, B, S):
        i32 = jnp.int32
        return {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32)}

    def batch_specs(self, kind: str, B: int, S: int) -> dict:
        """ShapeDtypeStruct stand-ins for every model input."""
        cfg = self.cfg
        bf16, i32 = jnp.bfloat16, jnp.int32
        if kind == "train" or kind == "prefill":
            if cfg.family == "vlm":
                return {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), bf16),
                        "positions": jax.ShapeDtypeStruct((3, B, S), i32),
                        "labels": jax.ShapeDtypeStruct((B, S), i32)}
            if cfg.family == "audio":
                half = S // 2
                return {"enc_frames": jax.ShapeDtypeStruct((B, half, cfg.d_model), bf16),
                        "tokens": jax.ShapeDtypeStruct((B, half), i32),
                        "labels": jax.ShapeDtypeStruct((B, half), i32)}
            return self._token_specs(B, S)
        if kind == "decode":
            if cfg.family == "vlm":
                return {"embeds": jax.ShapeDtypeStruct((B, 1, cfg.d_model), bf16),
                        "positions": jax.ShapeDtypeStruct((3, B, 1), i32)}
            return {"token": jax.ShapeDtypeStruct((B, 1), i32)}
        raise ValueError(kind)

    def make_batch(self, kind: str, B: int, S: int, key) -> dict:
        """Concrete random batch matching batch_specs (smoke tests)."""
        specs = self.batch_specs(kind, B, S)
        out = {}
        for i, (name, sds) in enumerate(sorted(specs.items())):
            k = jax.random.fold_in(key, i)
            if jnp.issubdtype(sds.dtype, jnp.integer):
                hi = self.cfg.vocab if name in ("tokens", "labels", "token") else max(S, 2)
                out[name] = jax.random.randint(k, sds.shape, 0, hi, sds.dtype)
            else:
                out[name] = (0.02 * jax.random.normal(k, sds.shape)).astype(sds.dtype)
        return out


def get_bundle(arch_id: str, reduced: bool = False, depth: int | None = None,
               **overrides) -> ModelBundle:
    """depth: override n_layers only (everything else stays full-size;
    encoder depth scales with it for enc-dec archs).
    ``overrides`` replace config fields: on the reduced sibling when
    ``reduced``, else on the published config."""
    import dataclasses
    cfg = get_config(arch_id)
    if reduced:
        cfg = cfg.reduced(**overrides)
    elif overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    if depth is not None:
        upd = {"n_layers": depth}
        if cfg.encoder_layers:
            upd["encoder_layers"] = depth
        cfg = dataclasses.replace(cfg, **upd)
    return ModelBundle(cfg)
