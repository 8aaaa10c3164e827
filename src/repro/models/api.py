"""Unified model API: dispatch per architecture family + input_specs.

``get_model(cfg)`` returns a `Model` bundle of pure functions with a single
signature convention shared by the trainer, the serving engine and the
multi-pod dry-run.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, InputShape
from repro.models import encdec, lm, xlstm, zamba

# sliding window used for long-context decode of full-attention archs
LONG_WINDOW = 8192
# number of image patches in VLM training batches (frontend stub)
VLM_PATCHES = 256
# whisper target length during training
WHISPER_TGT = 448


class Model(NamedTuple):
    init: Callable
    logical_axes: Callable
    loss_fn: Callable
    init_cache: Callable
    cache_logical_axes: Callable
    prefill: Callable
    decode_step: Callable
    # chunked batched prefill for serving (KV-cache / recurrent-state archs
    # that can ingest a prompt chunk in one launch); None -> the engine
    # falls back to sequential token-by-token prefill
    prefill_chunk: Optional[Callable] = None
    # ``update_buffers(buffers, metrics, cfg)``: the after-step update of
    # state kept outside the optimizer (``train/steps.BUFFERS``)
    update_buffers: Optional[Callable] = None


def get_model(cfg: ArchConfig) -> Model:
    if cfg.family in ("dense", "moe", "vlm"):
        m = lm
    elif cfg.family == "ssm":
        m = xlstm
    elif cfg.family == "hybrid":
        m = zamba
    elif cfg.family == "audio":
        m = encdec
    else:
        raise ValueError(cfg.family)
    return Model(m.init, m.logical_axes, m.loss_fn, m.init_cache,
                 m.cache_logical_axes, m.prefill, m.decode_step,
                 getattr(m, "prefill_chunk", None),
                 getattr(m, "update_buffers", None))


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def train_batch_specs(cfg: ArchConfig, shape: InputShape):
    """Batch pytree for loss_fn/train_step (no device allocation)."""
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "audio":
        return {"audio_emb": _sds((B, S, cfg.d_model), jnp.float32),
                "tokens": _sds((B, WHISPER_TGT), jnp.int32)}
    if cfg.family == "vlm":
        n_tok = S - VLM_PATCHES
        return {"tokens": _sds((B, n_tok), jnp.int32),
                "embeds": _sds((B, VLM_PATCHES, cfg.d_model), jnp.float32),
                "positions": _sds((3, B, S), jnp.int32)}
    return {"tokens": _sds((B, S), jnp.int32)}


def decode_specs(cfg: ArchConfig, shape: InputShape):
    """(tokens1, cache, pos) pytree specs for serve_step."""
    B, S = shape.global_batch, shape.seq_len
    model = get_model(cfg)
    window = decode_window(cfg, shape)
    max_len = min(S, window) if window else S
    cache = jax.eval_shape(
        lambda: model.init_cache(cfg, B, max_len, jnp.bfloat16))
    tokens1 = _sds((B, 1), jnp.int32)
    pos = _sds((), jnp.int32)
    extra = {}
    if cfg.mrope:
        extra["positions"] = _sds((3, B, 1), jnp.int32)
    return tokens1, cache, pos, extra


def prefill_specs(cfg: ArchConfig, shape: InputShape):
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "audio":
        return {"audio_emb": _sds((B, S, cfg.d_model), jnp.float32)}
    if cfg.family == "vlm":
        return {"tokens": _sds((B, S), jnp.int32),
                "positions": _sds((3, B, S), jnp.int32)}
    return {"tokens": _sds((B, S), jnp.int32)}


def decode_window(cfg: ArchConfig, shape: InputShape) -> int:
    """Sliding window for long-context decode of full-attention archs.
    0 = no window (full cache)."""
    if shape.name == "long_500k" and not cfg.subquadratic \
            and cfg.family != "audio":
        return LONG_WINDOW
    return 0


def decode_supported(cfg: ArchConfig, shape: InputShape) -> bool:
    """Which (arch, shape) decode pairs exist (DESIGN.md shape notes)."""
    if shape.kind != "decode":
        return True
    if not cfg.decode_supported:
        return False
    if shape.name == "long_500k" and cfg.family == "audio":
        return False            # whisper: no 500k decode (DESIGN.md skip)
    return True
