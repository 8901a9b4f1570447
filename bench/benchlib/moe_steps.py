"""What the latent-MoE cell's readers share: the model steps of the
`run()` calls inside the trace, each with the distinct routed experts its
routed layers selected, and their work (`bench/work/latent_moe_step.py`).

The window records, beside each batch, the distinct experts its steps
selected over all routed layers (`w["experts_routed"]`, from the engine's
counter).  A batch's steps all have its rows, so each is given the
batch's mean per routed layer and step.
"""
from __future__ import annotations

from . import cells, layers, serve_steps


def traced_steps(ctx: dict) -> list:
    """(rows, attended positions, experts read a routed layer) of each
    model step of the traced `run()` calls; empty where the window has no
    counter."""
    w = ctx["window"]
    routed = w.get("experts_routed") or []
    n = ctx["inputs"].get("routed_layers")
    if not n or len(routed) != len(w.get("batches", [])):
        return []
    out = []
    for inside, b, e in zip(layers._traced(ctx, "run"), w["batches"],
                            routed):
        if inside:
            st = serve_steps.steps(b)
            out += [(r, a, e / (len(st) * n)) for r, a in st]
    return out


def step_work(ctx: dict, rows: int, attended: int, experts: float) -> dict:
    inp = ctx["inputs"]
    m = inp["m"]
    keys = ("n_layers", "first_k_dense", "d_model", "n_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "d_ff",
            "moe_d_ff", "n_experts", "n_shared_experts", "top_k", "vocab")
    return cells.module("work", "latent_moe_step",
                        ctx["cell"].bench_dir).work(
        batch=rows, attended=attended, experts_read=experts,
        param_bytes=inp["param_bytes"], router_bytes=4,
        cache_bytes=inp["cache_bytes"], logit_bytes=inp["logit_bytes"],
        **{k: m[k] for k in keys})
