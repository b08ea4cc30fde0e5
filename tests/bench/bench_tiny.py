"""Tiny stand-ins for the benchmark's cells, small enough for the CPU.

Each keeps its cell's driver, limits and metric entries and swaps in a
2-layer model of the same family and short rows.
"""
from __future__ import annotations

import dataclasses

from bench import spec

TINY_PROGRAM = {
    "name": "tiny", "family": "ssm", "n_layers": 2, "d_model": 64,
    "n_heads": 0, "n_kv_heads": 0, "d_ff": 0, "vocab": 256,
    "ssm_state": 16, "ssm_expand": 2, "ssm_headdim": 16, "ssm_chunk": 64,
    "ssm_conv": 4, "norm_eps": 1e-6,
}


def tiny_cell(workload: str, **traffic) -> spec.Cell:
    cell = spec.cell(workload)
    config = dict(cell.config, vocab_size=TINY_PROGRAM["vocab"] - 3,
                  program=dict(TINY_PROGRAM,
                               tie_embeddings=cell.config["tie_embeddings"]))
    t = dict(cell.traffic)
    if t["driver"] == "train":
        t.update(seq_len=128, batch_per_chip=2, batch_pool=8)
    t.update(traffic)
    return dataclasses.replace(cell, config=config, traffic=t)
