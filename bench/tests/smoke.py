"""Smoke-size configurations and mixes for rehearsing the benchmark on
the CPU (the timed cells use the files under bench/configs and
bench/traffic)."""

from __future__ import annotations

import copy
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODEL = {
    "family": "dense", "num_layers": 2, "d_model": 64, "num_heads": 4,
    "num_kv_heads": 2, "d_ff": 128, "vocab_size": 256, "head_dim": 16,
    "rope_theta": 10000.0, "sliding_window": 4096, "norm_eps": 1e-05,
    "ffn_activation": "swiglu", "tie_embeddings": False,
}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def config(name: str) -> dict:
    """The named configuration file at smoke widths."""
    spec = load("BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == name)
    cfg = load(entry["file"])
    model = dict(MODEL, sliding_window=cfg["model"]["sliding_window"] and 4096,
                 rope_theta=cfg["model"]["rope_theta"])
    cfg = dict(cfg, model=model)
    cfg["reduced"] = sorted(set(cfg["reduced"]) | {
        k for k in model if k != "family"})
    return cfg


def mix(name: str) -> dict:
    """The named traffic mix at smoke lengths."""
    m = copy.deepcopy(load("bench", "traffic", f"{name}.json"))
    if m["loop"] == "serve_open_loop":
        m["serve"].update(slots=4, max_seq=160, prompt_window=96, page_size=8)
        if "prefixes" in m:
            m["prefixes"]["lengths"] = [16, 24, 32, 40]
            m["prompt"].update(median=8, min=2, max=24)
        else:
            m["prompt"].update(median=40, min=16, max=96, multiple=16)
        m["output"].update(median=8, min=2, max=16)
        m["arrivals"]["rate_per_s"] = 20.0
        m["check"].update(sample_tokens=40, sample_requests=3)
    else:
        m["train"].update(global_batch=4, seq_len=32)
    return m


TRAIN_CELL = "train.h2o-danube-1.8b.dp2-tp2"


def checkout_with_train(dest) -> str:
    """A copy of the benchmark in ``dest`` whose four-chip train cell has
    limits for smoke size (the cell's own are set at full size on the
    chip): on the CPU the program's first-step gradients sit within 0.003
    of the reference's, and a rank's half batch reads 0.05 and above.
    Returns the checkout's root."""
    root = os.path.join(str(dest), "checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"), dirs_exist_ok=True)
    limits = os.path.join(root, "bench", "limits", f"{TRAIN_CELL}.json")
    with open(limits, "w") as f:
        json.dump({"loss_gap": 0.01, "grad_gap": 0.03, "change_gap": 0.03}, f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root
