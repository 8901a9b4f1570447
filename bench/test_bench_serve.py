"""Tests of the served-model cell on the CPU, at the smoke size of its
model: the adapter's set-up, window and check driven through the runner
(without the look for a TPU), the plain reference against the program's
full-sequence forward pass, the comparison shown to fail for the float8
control and for faults planted in the timed path, the step's work
counted by hand, the request schedule, and the per-layer readers on
synthetic inputs."""
from __future__ import annotations

import dataclasses
import io
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchlib import cells, lm_traffic, lm_weights, ref_lm, runner  # noqa: E402
from benchlib import serve_steps  # noqa: E402

WORKLOAD = "granite_alpaca_steady"
PROMPT, NEW = 12, 6


def _plain(v):
    import jax.numpy as jnp
    if isinstance(v, type) or hasattr(v, "dtype"):
        return jnp.dtype(v).name
    return v


@pytest.fixture
def smoke(monkeypatch):
    """The cell at the smoke size of its model (float32, as the smoke
    config computes), with the registry handing out that size."""
    from repro.models import registry
    cfg, _ = registry.get("granite-3-2b", smoke=True)
    orig = registry.get
    monkeypatch.setattr(registry, "get",
                        lambda arch, smoke=False: orig(arch, smoke=True))
    cell = cells.load(ROOT, WORKLOAD)
    m = {f.name: _plain(getattr(cfg, f.name))
         for f in dataclasses.fields(cfg)}
    cell.config = dict(cell.config, model=m,
                       serve=dict(cell.config["serve"], param_dtype="float32",
                                  batch_slots=2, max_len=PROMPT + NEW))
    cell.traffic = dict(cell.traffic, checked=1000, prompt=PROMPT, new=NEW,
                        arrivals={"kind": "poisson", "rate_per_s": 40.0})
    return cell


def run(cell, keep=None):
    return runner.run(cell, seed=2**33 + 19, seconds=1.0, traced=False,
                      platform="cpu", out=io.StringIO(), err=io.StringIO(),
                      keep=keep)


def failed_checks(line):
    return [n for n, c in line["checks"].items() if c["value"] > c["limit"]]


# ---------------------------------------------------------------------------
# the run, the control and the planted faults
# ---------------------------------------------------------------------------

def test_program_passes_and_fp8_control_fails(smoke):
    keep = {}
    line = run(smoke, keep)
    assert line["correct"], line["checks"]
    w = keep["window"]
    assert line["attempted"] == 40 and line["failed"] == 0
    assert len(w["done"]) == 40
    assert all(len(d["tokens"]) == NEW for d in w["done"])
    assert w["notes"]["compiles_in_window"] == 0
    assert max(b[3] for b in w["batches"]) == 2
    assert set(line["metrics"]) == {"request_p50_ms", "setup_s"}
    inp = cells.module("adapters", "serve").layer_inputs(keep["state"], w)
    assert inp["setup_compile_s"] > 0 and inp["setup_weights_s"] > 0
    assert w["notes"]["compiles_in_setup"] > 0
    for d in w["done"]:
        assert d["latency_s"] >= d["wait_s"] >= 0.0
    adp = cells.module("adapters", "serve")
    checks = adp.check(keep["state"], w,
                       answer=adp.control_answer(smoke.config))
    assert [n for n, v, lim in checks if v > lim]


def _wrap_decode(monkeypatch, fn):
    from repro.models import transformer
    orig = transformer.decode_step
    monkeypatch.setattr(transformer, "decode_step",
                        lambda *a, **kw: fn(orig, *a, **kw))


def test_rope_position_off_by_one_in_decode(monkeypatch, smoke):
    import jax.numpy as jnp
    from repro.nn import attention
    orig = attention.rope

    def shifted(x, positions, theta):
        return orig(x, positions + (positions >= PROMPT).astype(jnp.int32),
                    theta)
    monkeypatch.setattr(attention, "rope", shifted)
    line = run(smoke)
    assert not line["correct"]
    assert failed_checks(line)


def test_cache_write_skipped(monkeypatch, smoke):
    """A step that returns its cache unchanged: no key or value of the
    new position is ever written."""
    def stale(orig, params, cfg, token, cache, cur_len, **kw):
        logits, _ = orig(params, cfg, token, cache, cur_len, **kw)
        return logits, cache
    _wrap_decode(monkeypatch, stale)
    line = run(smoke)
    assert not line["correct"]
    assert failed_checks(line)


def test_half_of_the_batch_left_out(monkeypatch, smoke):
    import jax

    def half(orig, params, cfg, token, cache, cur_len, **kw):
        b = token.shape[0]
        h = max(1, b // 2)
        idx = np.arange(b) % h
        logits, c = orig(params, cfg, token[:h],
                         jax.tree.map(lambda a: a[:, :h], cache), cur_len,
                         **kw)
        return logits[idx], jax.tree.map(lambda a: a[:, idx], c)
    _wrap_decode(monkeypatch, half)
    line = run(smoke)
    assert not line["correct"]
    assert failed_checks(line)


def test_token_altered_where_produced(monkeypatch, smoke):
    import jax.numpy as jnp

    def altered(orig, params, cfg, token, cache, cur_len, **kw):
        logits, c = orig(params, cfg, token, cache, cur_len, **kw)
        return jnp.where(cur_len == PROMPT + 1,
                         jnp.roll(logits, 1, axis=-1), logits), c
    _wrap_decode(monkeypatch, altered)
    line = run(smoke)
    assert not line["correct"]
    assert "logit_gap" in failed_checks(line)


def test_model_field_that_differs_from_the_file(smoke):
    smoke.config["model"] = dict(smoke.config["model"], rope_theta=5e5)
    line = run(smoke)
    assert not line["correct"]
    assert "config_drift" in failed_checks(line)


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

def test_reference_matches_the_programs_forward_at_float32():
    import jax
    import jax.numpy as jnp
    from repro.models import registry, transformer
    from repro.nn import core
    cfg, _ = registry.get("granite-3-2b", smoke=True)
    m = {f.name: _plain(getattr(cfg, f.name))
         for f in dataclasses.fields(cfg)}
    w = {"embed_std": 0.1, "norm_scale_std": 0.1}
    params = lm_weights.make_fn(m, w, jnp.float32)(lm_weights.key(5))
    toks = np.random.default_rng(5).integers(2, m["vocab"], (2, 20))
    with jax.default_matmul_precision("highest"):
        h, _ = transformer.forward(params, cfg, jnp.asarray(toks),
                                   remat=False)
        want = np.asarray(core.unembed_logits(params["embed"]["table"], h))
        got = ref_lm.Reference(m, 1e-6).logits(
            params, list(toks), [np.arange(20)] * 2)
    for g, x in zip(got, want):
        assert np.abs(g - x).max() <= 1e-4 * np.abs(x).max()


def test_fp8_rounding_keeps_the_scale():
    import jax.numpy as jnp
    a = jnp.asarray(np.random.default_rng(0).normal(0, 0.02, (64, 64)),
                    jnp.float32)
    q = np.asarray(ref_lm.fp8_round(a))
    rel = np.abs(q - np.asarray(a)) / np.abs(np.asarray(a)).max()
    assert 0 < rel.max() <= 2.0 ** -4
    assert np.abs(q).max() == pytest.approx(float(jnp.abs(a).max()),
                                            rel=1e-6)


def test_readings_of_served_tokens():
    adp = cells.module("adapters", "serve")
    ref = [np.array([[1.0, 4.0, -2.0], [0.5, -8.0, 3.0]], np.float32)]
    r = adp.readings(ref, [[1, 2]])
    assert r == {"not_finite": 0.0, "logit_gap": 0.0, "logit_gap_mean": 0.0}
    r = adp.readings(ref, [[0, 2]])
    assert r["logit_gap"] == pytest.approx(3.0 / 4.0)
    assert r["logit_gap_mean"] == pytest.approx(3.0 / 8.0)
    assert adp.readings(ref, [[1, 7]])["logit_gap"] == np.inf
    bad = [np.array([[np.nan, 1.0, 0.0]], np.float32)]
    assert adp.readings(bad, [[1]])["not_finite"] == 1.0


def test_sample_holds_the_longest_and_follows_the_seed():
    adp = cells.module("adapters", "serve")
    st = adp.State()
    st.mix, st.seed = {"checked": 3}, 11
    done = [{"req": {"prompt": np.zeros(n), "new": 2}} for n in
            (4, 4, 9, 4, 4, 4)]
    a = adp.sample(st, {"done": done})
    assert len(a) == 3 and a[0] is done[2]
    assert len({id(x) for x in a}) == 3
    assert [id(x) for x in adp.sample(st, {"done": done})] == \
        [id(x) for x in a]


def test_weights_follow_the_seed_and_the_layout():
    import jax.numpy as jnp
    m = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
         "head_dim": 4, "d_ff": 16, "vocab": 32}
    w = {"embed_std": 0.1, "norm_scale_std": 0.1}
    make = lm_weights.make_fn(m, w, jnp.bfloat16)
    a, b = make(lm_weights.key(2**33 + 1)), make(lm_weights.key(2**33 + 1))
    c = make(lm_weights.key(7))
    assert a["layers"]["mlp"]["wi"].shape == (2, 8, 16)
    assert a["layers"]["attn"]["wk"].shape == (2, 8, 1, 4)
    assert a["embed"]["table"].dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(a["embed"]["table"], np.float32),
                          np.asarray(b["embed"]["table"], np.float32))
    assert not np.array_equal(np.asarray(a["embed"]["table"], np.float32),
                              np.asarray(c["embed"]["table"], np.float32))
    assert float(jnp.std(a["layers"]["norm1"]["scale"].astype(
        jnp.float32))) > 0


# ---------------------------------------------------------------------------
# the request schedule
# ---------------------------------------------------------------------------

MIX = {"arrivals": {"kind": "poisson", "rate_per_s": 20.0},
       "prompt": 128, "new": 32, "pattern_seed": 4}


def test_fixed_length_schedule_is_the_same_for_every_seed():
    a = lm_traffic.schedule(MIX, 5.0, 2**33 + 3, 49155)
    b = lm_traffic.schedule(MIX, 5.0, 8, 49155)
    assert len(a) == len(b) == 100
    assert [(r["due"], len(r["prompt"]), r["new"]) for r in a] == \
        [(r["due"], len(r["prompt"]), r["new"]) for r in b]
    assert all(len(r["prompt"]) == 128 and r["new"] == 32 for r in a)
    assert any(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, b))
    toks = np.concatenate([r["prompt"] for r in a])
    assert toks.min() >= 2 and toks.max() < 49155
    again = lm_traffic.schedule(MIX, 5.0, 2**33 + 3, 49155)
    assert all(np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, again))


# ---------------------------------------------------------------------------
# the step's work and the readers
# ---------------------------------------------------------------------------

SHAPE = dict(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
             d_ff=16, vocab=32)


def test_lm_step_work_by_hand():
    w = cells.module("work", "lm_step").work(
        batch=3, attended=5, param_bytes=2, cache_bytes=4, logit_bytes=2,
        **SHAPE)
    # per layer: q 8x8, k and v 8x4 each, o 8x8 -> 192; MLP 3 x 8 x 16
    # -> 384; table 32 x 8 = 256
    matmul = 2 * (192 + 384) + 256
    assert w["flops"] == 2 * 3 * matmul + 4 * 3 * 2 * 2 * 4 * 5
    weights = (2 * (192 + 384 + 16) + 256 + 8) * 2
    kv = 2 * 3 * 2 * 1 * 4 * 4 * 6
    assert w["bytes"] == weights + kv + 3 * 32 * 2


def _ctx():
    """Two `run()` calls, the second inside the trace: 2 rows, a
    3-token prompt and 2 decode steps, device busy 4 of its 10 ns."""
    from benchlib import trace
    from benchlib.trace import Event
    dev, host = "/device:TPU:0", "/host:CPU"
    ev = [Event(host, "python", "bench:window", 0, 100),
          Event(host, "python", "bench:run", 50, 60),
          Event(dev, "XLA Modules", "jit__lambda(3)", 51, 54),
          Event(dev, "XLA Modules", "jit_argmax(4)", 55, 56),
          Event(dev, "XLA Ops", "fusion.1", 51, 54),
          Event(dev, "XLA Ops", "fusion.2", 55, 56)]
    m = dict(SHAPE, compute_dtype="bfloat16")
    done = [{"req": {"due": 0.1}, "wait_s": 0.2},
            {"req": {"due": 0.2}, "wait_s": 0.4},
            {"req": {"due": 2.1}, "wait_s": 0.0}]
    return {"cell": cells.Cell("x", 1, {}, {}, [], [], BENCH),
            "trace": trace.reduce(ev),
            "spans": [("run", 0.3, 1.0), ("run", 2.0, 2.5)],
            "traced_from_s": 1.5, "traced_until_s": 3.0,
            "peaks": cells.peaks("TPU v5 lite"),
            "inputs": {"m": m, "param_bytes": 2, "cache_bytes": 4,
                       "logit_bytes": 2, "setup_compile_s": 1.25,
                       "setup_weights_s": 0.5},
            "window": {"done": done,
                       "batches": [(0.3, 1.0, 2, 2, 3, 2),
                                   (2.0, 2.5, 1, 1, 3, 2)]}}


def _read(name, ctx):
    return cells.module("metrics", name).read(ctx)


def test_serve_readers_on_a_synthetic_trace():
    ctx = _ctx()
    assert serve_steps.traced_steps(ctx) == [(1, 1), (1, 2), (1, 3),
                                             (1, 4), (1, 5)]
    assert serve_steps.step_program_s(ctx) == pytest.approx(3e-9)
    assert _read("step_device_ms.serve", ctx) == pytest.approx(3e-9 * 1e3
                                                               / 5)
    assert _read("host_ms_per_step.serve", ctx) == pytest.approx(
        (10e-9 - 4e-9) * 1e3 / 5)
    assert _read("device_idle_share.serve", ctx) == pytest.approx(96.0)
    assert _read("batch_items_mean.serve", ctx) == 2.0
    assert _read("setup_compile_s.serve", ctx) == 1.25
    assert _read("setup_weights_s.serve", ctx) == 0.5
    assert _read("queue_wait_ms_p95.serve", ctx) == pytest.approx(
        float(np.quantile([200.0, 400.0], 0.95)))
    p = ctx["peaks"]
    ws = [serve_steps.step_work(ctx, r, a)
          for r, a in serve_steps.traced_steps(ctx)]
    least = sum(max(w["flops"] / p["bf16_flops_per_s"],
                    w["bytes"] / p["hbm_bytes_per_s"]) for w in ws)
    assert _read("step_roofline.serve", ctx) == pytest.approx(
        100 * least / 3e-9)
    assert _read("model_mfu.serve", ctx) == pytest.approx(
        100 * sum(w["flops"] for w in ws) / (100e-9 * p["bf16_flops_per_s"]))


def test_serve_readers_find_nothing_in_an_empty_trace():
    ctx = _ctx()
    ctx["window"]["batches"] = ctx["window"]["batches"][:1]
    ctx["spans"] = ctx["spans"][:1]
    for name in ("step_device_ms.serve", "host_ms_per_step.serve",
                 "step_roofline.serve", "model_mfu.serve"):
        assert _read(name, ctx) is None


# ---------------------------------------------------------------------------
# the scripts that read limits and the knee
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("script, args", [
    ("control_lm.py", ["--seeds", "1", "--seconds", "1"]),
    ("sweep.py", ["--rates", "1", "--seconds", "1", "--seed", "1"]),
])
def test_scripts_need_the_chip(script, args):
    """Started without a TPU, each exits non-zero and prints nothing."""
    import os
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / script), "--workload",
                        WORKLOAD, *args], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr
