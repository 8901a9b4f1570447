"""Adapter of a served language model: open-loop requests through the
program's serving engine, `serving.engine.Server.submit` / `run`.

Set-up takes the model from the program's registry (`arch`), compares
every field of it with the configuration file's `model` record
(`config_drift`, limit 0), serves it in the file's `serve` dtype, makes
the weights from the seed on the device in one jitted call
(`benchlib.lm_weights`), builds one `Server`, and warms every batch size
from 1 to `batch_slots` with requests the window never sends, so that
nothing compiles in the window (`compiles_in_window` counts JAX's
backend compiles there).

The window submits the requests that are due, at most `batch_slots` of
them, then calls `run()`: one call is one batch.  A request's latency
runs from its due time to the return of that call, with all its tokens
on the host.  A request that does not come back with exactly the tokens
it asked for has failed.

After the window a sample of the finished requests, drawn from the seed
with the longest among them, is run once through the plain reference
(`benchlib.ref_lm`) over its prompt and its served tokens, with weights
made again from the seed; each served token is judged by how far its
reference logit lies below the reference's best.
"""
from __future__ import annotations

import dataclasses
import time
import traceback

import numpy as np

from benchlib import lm_traffic, lm_weights, openloop, ref_lm

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class State:
    pass


class CompileCount:
    """JAX's backend compiles (persistent-cache reads among them) while
    registered: how many, and their seconds."""

    def __init__(self):
        import jax
        self.n, self.s = 0, 0.0
        self._mon = jax.monitoring
        self._mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration_secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.n += 1
            self.s += duration_secs

    def close(self) -> None:
        self._mon.unregister_event_duration_listener(self._on)


def _plain(v):
    """A config field as the file states it (dtypes by name)."""
    import jax.numpy as jnp
    if isinstance(v, type) or hasattr(v, "dtype"):
        return jnp.dtype(v).name
    return v


def model_drift(pcfg, m: dict) -> int:
    """How many fields of the program's config differ from the file's
    `model` record, or are missing from either."""
    prog = {f.name: _plain(getattr(pcfg, f.name))
            for f in dataclasses.fields(pcfg)}
    keys = set(prog) | set(m)
    return sum(prog.get(k, KeyError) != m.get(k, KeyError) for k in keys)


def layout_drift(model, scfg, params) -> int:
    """Leaves of the generated weights whose path, shape or dtype the
    program's own `init` would not give."""
    import jax
    want = jax.eval_shape(lambda k: model.init(k, scfg),
                          jax.random.PRNGKey(0))
    a = jax.tree_util.tree_flatten_with_path(want)[0]
    b = jax.tree_util.tree_flatten_with_path(params)[0]
    if [p for p, _ in a] != [p for p, _ in b]:
        return max(len(a), len(b))
    return sum(x.shape != y.shape or x.dtype != y.dtype
               for (_, x), (_, y) in zip(a, b))


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
    st.compiles = CompileCount()
    pcfg, model = registry.get(cfg["arch"])
    st.drift = model_drift(pcfg, st.m)
    dtype = jnp.dtype(st.sv["param_dtype"])
    scfg = dataclasses.replace(pcfg, param_dtype=dtype)
    st.make = lm_weights.make_fn(st.m, cfg["weights"], dtype)
    t0 = time.perf_counter()
    params = jax.block_until_ready(st.make(lm_weights.key(seed)))
    st.weights_s = time.perf_counter() - t0
    st.drift += layout_drift(model, scfg, params)
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
    reqs = lm_traffic.schedule(st.mix, seconds, st.seed, st.m["vocab"])
    srv, slots = st.server, int(st.sv["batch_slots"])
    comp0 = st.compiles.n
    done, batches = [], []
    failed, late = 0, 0.0
    longest = (0.0, 0.0, 0, 0.0)
    i = 0
    t0 = time.perf_counter()
    while i < len(reqs):
        now = time.perf_counter() - t0
        if reqs[i]["due"] > now:
            time.sleep(reqs[i]["due"] - now)
            late = max(late, time.perf_counter() - t0 - reqs[i]["due"])
            continue
        j = i
        while j < len(reqs) and j - i < slots and reqs[j]["due"] <= now:
            j += 1
        batch = {r["i"]: r for r in reqs[i:j]}
        i = j
        for r in batch.values():
            srv.submit(st.Request(r["i"], r["prompt"],
                                  max_new_tokens=r["new"]))
        dec0 = srv.stats.decode_steps
        start = time.perf_counter() - t0
        cpu0 = time.thread_time()
        try:
            with span("run"):
                fin = srv.run()
        except Exception:               # a failed batch fails its items
            traceback.print_exc()
            srv.queue.clear()
            failed += len(batch)
            continue
        end = time.perf_counter() - t0
        prompt_steps = max(len(r["prompt"]) for r in batch.values())
        batches.append((start, end, len(fin), len(batch), prompt_steps,
                        srv.stats.decode_steps - dec0))
        longest = max(longest, (end - start, time.thread_time() - cpu0,
                                len(batch), start))
        for q in fin:
            req = batch.pop(q.rid, None)
            if req is None or len(q.out_tokens) != req["new"]:
                failed += 1
                continue
            done.append({"req": req, "tokens": [int(t) for t in q.out_tokens],
                         "latency_s": end - req["due"],
                         "wait_s": start - req["due"]})
        failed += len(batch)            # carried, never returned
    lat = [d["latency_s"] for d in done]
    p95 = float(np.quantile(lat, 0.95)) if lat else 0.0
    starts = [d["req"]["due"] + d["wait_s"] for d in done]
    ends = [d["req"]["due"] + d["latency_s"] for d in done]
    return {"attempted": len(reqs), "failed": failed, "done": done,
            "batches": batches,
            "notes": {
                "requests": len(reqs), "completed": len(done),
                "batches": len(batches),
                "items_per_batch": len(done) / max(1, len(batches)),
                "latency_samples": len(lat),
                "request_p95_ms": 1e3 * p95,
                "beyond_p95": sum(x > p95 for x in lat),
                "compiles_in_setup": st.compiles_setup,
                "compiles_in_window": st.compiles.n - comp0,
                "queued_at_close": sum(s > seconds for s in starts),
                "in_flight_at_close": sum(s <= seconds < e
                                          for s, e in zip(starts, ends)),
                "last_completion_s": max(ends, default=0.0),
                "generator_late_ms_max": 1e3 * late,
                "longest_run": "%.4f s wall, %.4f s thread cpu, %d items, "
                               "from %.2f s" % longest}}


def end_to_end(st: State, w: dict) -> dict:
    lat = [d["latency_s"] * 1e3 for d in w["done"]]
    if not lat:
        return {}
    return {"request_p50_ms": float(np.quantile(lat, 0.50))}


def layer_inputs(st: State, w: dict) -> dict:
    import jax.numpy as jnp
    return {"m": st.m,
            "param_bytes": jnp.dtype(st.sv["param_dtype"]).itemsize,
            "cache_bytes": jnp.dtype(st.sv["kv_cache_dtype"]).itemsize,
            "logit_bytes": jnp.dtype(st.m["compute_dtype"]).itemsize,
            "setup_compile_s": st.compile_s_setup,
            "setup_weights_s": st.weights_s}


def release(st: State) -> None:
    st.server = None
    st.compiles.close()


def sample(st: State, w: dict) -> list:
    """The finished requests to check: the longest, then others drawn
    from the seed, `checked` in all."""
    done = w["done"]
    k = min(int(st.mix["checked"]), len(done))
    if not k:
        return []
    size = [len(d["req"]["prompt"]) + d["req"]["new"] for d in done]
    first = int(np.argmax(size))
    rest = [j for j in range(len(done)) if j != first]
    r = openloop.rng(st.seed, 99)
    pick = r.choice(len(rest), k - 1, replace=False) if k > 1 else []
    return [done[first]] + [done[rest[j]] for j in pick]


COMPARED = ("not_finite", "logit_gap", "logit_gap_mean")


def readings(ref: list, tokens: list) -> dict:
    """Per request, the reference's logits at each served position
    (`ref[j]`, (N, V)) against the token served there (`tokens[j]`):
    by how far the served token's logit lies below the best, as a share
    of the position's largest magnitude, the widest gap and the mean
    over every served token."""
    gaps, finite = [], True
    for lg, toks in zip(ref, tokens):
        finite &= bool(np.isfinite(lg).all())
        for row, t in zip(lg, toks):
            if not 0 <= t < row.shape[0]:
                gaps.append(np.inf)
                continue
            gaps.append((float(row.max()) - float(row[t]))
                        / max(float(np.abs(row).max()), 1e-30))
    return {"not_finite": 0.0 if finite else 1.0,
            "logit_gap": max(gaps, default=np.inf),
            "logit_gap_mean": float(np.mean(gaps)) if gaps else np.inf}


def check(st: State, w: dict, answer=None) -> list:
    """Compare the sample's served tokens with the reference.  `answer(
    params, seqs, rows)`, when given, puts its own token at each served
    position in the program's place (the control)."""
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
    params = jax.block_until_ready(st.make(lm_weights.key(st.seed)))
    with jax.default_matmul_precision("highest"):
        ref = ref_lm.Reference(st.m, st.cfg["equations"]["rms_norm_eps"]
                               ).logits(params, seqs, rows)
        if answer is not None:
            served = answer(params, seqs, rows)
    del params
    r = readings(ref, served)
    return out + [(n, r[n], lim.get(n, 0.0)) for n in COMPARED]


def control_answer(cfg: dict):
    """The control: the reference computed in float8_e4m3, a precision
    below the bfloat16 the configuration serves in: every weight matrix
    (one scale a tensor) and every activation entering one (one scale a
    token) rounded to it, products accumulated in float32.  At each
    served position, the token it puts first."""
    def answer(params, seqs, rows):
        lo = ref_lm.Reference(cfg["model"], cfg["equations"]["rms_norm_eps"],
                              weights=ref_lm.fp8_round,
                              inputs=ref_lm.fp8_rows)
        return [[int(t) for t in lg.argmax(axis=1)]
                for lg in lo.logits(params, seqs, rows)]
    return answer
