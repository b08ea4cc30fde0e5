"""Family → model-function dispatch."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

from repro.models import mamba2, transformer, whisper, zamba2
from repro.models.base import ModelConfig


@dataclasses.dataclass(frozen=True)
class Model:
    """Functional model bundle for one architecture."""

    cfg: ModelConfig
    init: Callable          # (key) -> params
    loss: Callable          # (params, batch, *, gather=None) -> scalar
    loss_aux: Callable      # (params, batch, *, gather=None) -> (scalar, counters)
    aux_names: tuple        # the counters' names: ("moe_rows",) or ()
    prefill: Callable       # (params, batch, *, gather=None) -> (logits, cache)
    decode: Callable        # (params, token, cache, *, gather=None) -> (logits, cache)
    init_cache: Callable    # (batch_size, max_seq) -> cache


_FAMILIES = {
    "dense": transformer, "moe": transformer, "vlm": transformer,
    "ssm": mamba2, "hybrid": zamba2, "audio": whisper,
}


def get_model(cfg: ModelConfig) -> Model:
    mod: Any = _FAMILIES[cfg.family]
    if mod is transformer:
        def loss_aux(params, batch, **kw):
            return transformer.loss_and_aux(cfg, params, batch, **kw)
    else:
        def loss_aux(params, batch, **kw):
            return mod.loss_fn(cfg, params, batch, **kw), {}
    return Model(
        cfg=cfg,
        init=lambda key: mod.init_params(cfg, key),
        loss=lambda params, batch, **kw: mod.loss_fn(cfg, params, batch, **kw),
        loss_aux=loss_aux,
        aux_names=("moe_rows",) if cfg.dropless and mod is transformer
        else (),
        prefill=lambda params, batch, **kw: mod.prefill(cfg, params, batch,
                                                        **kw),
        decode=lambda params, token, cache, **kw: mod.decode_step(
            cfg, params, token, cache, **kw),
        init_cache=lambda bs, max_seq, **kw: mod.init_cache(cfg, bs, max_seq,
                                                            **kw),
    )
