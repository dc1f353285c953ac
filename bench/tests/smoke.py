"""Cells at the configurations' smoke sizes, for CPU rehearsals."""
from __future__ import annotations

import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

SMOKE_MODEL = {
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 512, "vocab_rows": 512, "diag_block": 16,
}


def _load(*parts):
    with open(BENCH.joinpath(*parts)) as f:
        return json.load(f)


def smoke_config(name: str, overrides=None) -> dict:
    """``bench/configs/<name>.json`` cut to the program's smoke preset."""
    conf = copy.deepcopy(_load("configs", f"{name}.json"))
    conf["model"].update(SMOKE_MODEL)
    conf["program"]["smoke"] = True
    conf["program"].setdefault("overrides", {}).update(overrides or {})
    return conf


def backlog_cell(limit: float = 1e9) -> dict:
    mix = {"driver": "serve", "why": "smoke",
           "pool": {"slots": 4, "segment": 4, "max_len": 64},
           "arrivals": {"kind": "backlog", "per_second": 400},
           "prompt_len": {"kind": "choice", "values": [16, 32],
                          "weights": [1, 1]},
           "gen_len": {"kind": "uniform", "min": 4, "max": 12},
           "sample": {"tokens": 24, "max_requests": 4}}
    return {"name": "smoke-backlog", "chips": 1,
            "config": smoke_config("stablelm-1.6b-lln_diag"), "mix": mix,
            "limits": {"max_logit_gap": limit}}


def train_cell(limits=None) -> dict:
    mix = copy.deepcopy(_load("traffic", "mlm512.json"))
    mix.update({"batch": 4, "seq": 32, "batches": 4})
    return {"name": "smoke-train", "chips": 1,
            "config": smoke_config("roberta-lln", {"use_kernel": True}),
            "mix": mix,
            "limits": limits or {"loss_gap": 1e9, "grad_norm_gap": 1e9,
                                 "update_norm_gap": 1e9}}
