"""A tiny cell of the configuration for CPU rehearsals: the same
files' structure, every size cut down."""
from __future__ import annotations

import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def qwen2_cell(traffic: str = "decode_backlog"):
    from harness import Cell

    c = _load("configs", "qwen2-1.5b.json")
    c.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, vocab_size=512)
    c["program"] = {"arch": "qwen2-1.5b", "overrides": dict(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
        vocab_size=512)}
    c["ds_head"] = dict(c["ds_head"], num_experts=4)
    c["serve"] = dict(c["serve"], prefill_chunk=16)
    c["correct"] = {"logit_gap_limit": 0.01}
    t = _tiny_traffic(_load("traffic", traffic + ".json"))
    return Cell("tiny.qwen2", c, t)


def mamba2_cell(traffic: str = "chat_bursty"):
    from harness import Cell

    c = _load("configs", "mamba2-130m.json")
    c.update(d_model=64, n_layer=2, vocab_size=512)
    c["defaults"] = dict(c["defaults"], d_state=16, headdim=16, chunk_size=8)
    c["program"] = {"arch": "mamba2-130m", "overrides": dict(
        n_layers=2, d_model=64, vocab_size=512, ssm_state=16, ssm_headdim=16,
        ssm_chunk=8)}
    c["ds_head"] = dict(c["ds_head"], num_experts=4)
    c["serve"] = dict(c["serve"], prefill_chunk=16)
    c["correct"] = {"logit_gap_limit": 0.01}
    t = _tiny_traffic(_load("traffic", traffic + ".json"))
    if t["kind"] == "open":
        t.update(rate=40.0, warm_s=0.5, block=16)
    return Cell("tiny.mamba2", c, t)


def _tiny_traffic(t: dict) -> dict:
    t = copy.deepcopy(t)
    t.update(slots=4, max_seq_len=64, block=8)
    t["prompt"] = dict(median=12, sigma=0.5, min=4, max=40)
    t["output"] = dict(median=8, sigma=0.5, min=4, max=20)
    return t
