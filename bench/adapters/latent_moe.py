"""Adapter of a served latent-attention, routed-expert language model (the
program's `LatentMoEConfig`): open-loop requests through
`serving.engine.Server.submit` / `run`, as the `serve` adapter drives a
dense model, whose window, timing and readings it uses unchanged.

What differs is the model.  The registry holds the published configuration;
the file's `cut` replaces the keys that `reduced` names (the depth), and
every field of the result is compared with the file's `model` record
(`config_drift`, limit 0).  The weights come from
`benchlib.latent_moe_weights`, and the served tokens are judged against
this layout's own plain reference, `benchlib.ref_latent_moe`.

The engine reads the step's routing counter into
`ServeStats.experts_routed` once a `run()` call.  The window reads it after
each call, so each batch carries the distinct experts its steps selected
(`w["experts_routed"]`, beside `w["batches"]`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from benchlib import cells, latent_moe_weights, ref_latent_moe

serve = cells.module("adapters", "serve")
State = serve.State
end_to_end, release, sample = serve.end_to_end, serve.release, serve.sample
# Printed and not compared (PERF.md, the limits table): `logit_gap`, the
# widest single shortfall, because one expert selection flipped by
# bfloat16 rounding sends a token as far below the reference's best as
# float8 does; `argmax_miss_share`, because with random weights the first
# choice changes on rounding, so bfloat16's own share lies too near
# float8's for a limit with room on both sides
COMPARED = ("not_finite", "logit_gap_mean")
NOTED = ("logit_gap", "argmax_miss_share")


def readings(ref: list, tokens: list) -> dict:
    """The `serve` readings, and the share of served tokens that are not
    the reference's first choice at their position."""
    r = serve.readings(ref, tokens)
    miss = [int(t) != int(row.argmax())
            for lg, toks in zip(ref, tokens) for row, t in zip(lg, toks)]
    r["argmax_miss_share"] = float(np.mean(miss)) if miss else np.inf
    return r


def setup(cell, seed: int) -> State:
    import jax
    import jax.numpy as jnp
    from repro.models import registry
    from repro.serving.engine import Request, Server
    cfg, mix = cell.config, cell.traffic
    st = State()
    st.cfg, st.mix, st.seed = cfg, mix, seed
    st.m, st.sv = cfg["model"], cfg["serve"]
    st.Request = Request
    st.compiles = serve.CompileCount()
    pub, model = registry.get(cfg["arch"])
    pcfg = dataclasses.replace(pub, **cfg["cut"])
    st.drift = serve.model_drift(pcfg, st.m)
    dtype = jnp.dtype(st.sv["param_dtype"])
    scfg = dataclasses.replace(pcfg, param_dtype=dtype)
    st.make = latent_moe_weights.make_fn(st.m, cfg["weights"], dtype)
    t0 = time.perf_counter()
    params = jax.block_until_ready(st.make(latent_moe_weights.key(seed)))
    st.weights_s = time.perf_counter() - t0
    st.drift += serve.layout_drift(model, scfg, params)
    slots = int(st.sv["batch_slots"])
    st.server = Server(scfg, model, params, batch_slots=slots,
                       max_len=int(st.sv["max_len"]), eos=int(st.sv["eos"]))
    for b in range(1, slots + 1):
        for k in range(b):
            st.server.submit(Request(-1 - k, np.full(1, 2, np.int32),
                                     max_new_tokens=2))
        st.server.run()
    st.compiles_setup, st.compile_s_setup = st.compiles.n, st.compiles.s
    return st


def window(st: State, seconds: float, span) -> dict:
    """The `serve` window, with the engine's routing counter read after
    each `run()` call that returned."""
    stats = st.server.stats
    marks = [stats.experts_routed]

    @contextlib.contextmanager
    def counted(name):
        with span(name):
            yield
        marks.append(stats.experts_routed)

    w = serve.window(st, seconds, counted)
    w["experts_routed"] = [b - a for a, b in zip(marks, marks[1:])]
    w["notes"]["experts_routed"] = marks[-1] - marks[0]
    return w


def layer_inputs(st: State, w: dict) -> dict:
    return dict(serve.layer_inputs(st, w),
                routed_layers=st.m["n_layers"] - st.m["first_k_dense"])


def check(st: State, w: dict, answer=None) -> list:
    """Compare the sample's served tokens with the reference, as the
    `serve` adapter does.  `answer(params, seqs, rows)`, when given, puts
    its own token at each served position in the program's place (the
    control)."""
    import jax
    picked = sample(st, w)
    out = [("config_drift", float(st.drift), 0.0),
           ("unchecked", 0.0 if picked else 1.0, 0.0)]
    lim = st.cfg["limits"]
    if not picked:
        return out + [(n, np.inf, lim.get(n, 0.0)) for n in COMPARED]
    seqs, rows, served = [], [], []
    for d in picked:
        p, toks = d["req"]["prompt"], d["tokens"]
        seqs.append(np.concatenate([p, np.asarray(toks[:-1], np.int32)]))
        rows.append(np.arange(len(p) - 1, len(p) - 1 + len(toks)))
        served.append(toks)
    params = jax.block_until_ready(st.make(latent_moe_weights.key(st.seed)))
    with jax.default_matmul_precision("highest"):
        ref = ref_latent_moe.Reference(
            st.m, st.cfg["equations"]["rms_norm_eps"]).logits(params, seqs,
                                                              rows)
        if answer is not None:
            served = answer(params, seqs, rows)
    del params
    r = readings(ref, served)
    w.setdefault("notes", {}).update((n, r[n]) for n in NOTED)
    return out + [(n, r[n], lim.get(n, 0.0)) for n in COMPARED]


def control_answer(cfg: dict):
    """The control: the reference computed in float8_e4m3, a precision
    below the bfloat16 the configuration serves in: every weight matrix
    (one scale a matrix, each expert's apart) and every activation
    entering one (one scale a token) rounded to it, products accumulated
    in float32.  At each served position, the token it puts first."""
    from benchlib import ref_lm

    def answer(params, seqs, rows):
        lo = ref_latent_moe.Reference(
            cfg["model"], cfg["equations"]["rms_norm_eps"],
            weights=ref_lm.fp8_round, inputs=ref_lm.fp8_rows)
        return [[int(t) for t in lg.argmax(axis=1)]
                for lg in lo.logits(params, seqs, rows)]
    return answer
