"""Tests of the latent-MoE served cell on the CPU, at the smoke size of its
model: the adapter's set-up, window and check driven through the runner
(without the look for a TPU), the bench's plain reference against the
program's full-sequence forward pass, the comparison shown to fail for the
float8 control and for faults planted in the timed path, the weights'
layout, the step's work counted by hand, and the per-layer readers on
synthetic inputs."""
from __future__ import annotations

import dataclasses
import io
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchlib import cells, latent_moe_weights, moe_steps  # noqa: E402
from benchlib import ref_latent_moe, runner  # noqa: E402

WORKLOAD = "moonlight_alpaca_steady"
ARCH = "moonlight-16b-a3b"
PROMPT, NEW = 12, 6


def _plain(v):
    import jax.numpy as jnp
    if isinstance(v, type) or hasattr(v, "dtype"):
        return jnp.dtype(v).name
    return v


def _record(cfg) -> dict:
    return {f.name: _plain(getattr(cfg, f.name))
            for f in dataclasses.fields(cfg)}


# At the smoke size the program computes in float32 and its readings are
# 0 (every served token the reference's first choice); the file's limits
# are those of bfloat16 at the published widths, where routing flips
# under rounding.  This leaves room above 0 and is far below any fault's.
SMOKE_LIMITS = {"logit_gap_mean": 1e-3}


@pytest.fixture
def smoke(monkeypatch):
    """The cell at the smoke size of its model (float32, as the smoke
    config computes, with limits for float32), with the registry handing
    out that size."""
    from repro.models import registry
    cfg, _ = registry.get(ARCH, smoke=True)
    orig = registry.get
    monkeypatch.setattr(registry, "get",
                        lambda arch, smoke=False: orig(arch, smoke=True))
    cell = cells.load(ROOT, WORKLOAD)
    cell.config = dict(cell.config, model=_record(cfg),
                       cut={"n_layers": cfg.n_layers}, limits=SMOKE_LIMITS,
                       serve=dict(cell.config["serve"], param_dtype="float32",
                                  batch_slots=2, max_len=PROMPT + NEW))
    cell.traffic = dict(cell.traffic, checked=1000, prompt=PROMPT, new=NEW,
                        arrivals={"kind": "poisson", "rate_per_s": 40.0})
    return cell


def run(cell, keep=None):
    return runner.run(cell, seed=2**33 + 23, seconds=1.0, traced=False,
                      platform="cpu", out=io.StringIO(), err=io.StringIO(),
                      keep=keep)


def failed_checks(line):
    return [n for n, c in line["checks"].items() if c["value"] > c["limit"]]


def adapter():
    return cells.module("adapters", "latent_moe")


# ---------------------------------------------------------------------------
# the run, the control and the planted faults
# ---------------------------------------------------------------------------

def test_program_passes_and_fp8_control_fails(smoke):
    keep = {}
    line = run(smoke, keep)
    assert line["correct"], line["checks"]
    w = keep["window"]
    assert line["attempted"] == 40 and line["failed"] == 0
    assert all(len(d["tokens"]) == NEW for d in w["done"])
    assert w["notes"]["compiles_in_window"] == 0
    assert set(line["metrics"]) == {"request_p50_ms", "setup_s"}
    assert line["checks"]["config_drift"]["value"] == 0.0
    for n in ("logit_gap", "argmax_miss_share"):
        assert n not in line["checks"] and w["notes"][n] == 0.0
    # the counter: per batch, between top_k and rows * top_k distinct
    # experts a routed layer and step
    assert len(w["experts_routed"]) == len(w["batches"])
    for e, b in zip(w["experts_routed"], w["batches"]):
        steps = (b[4] + b[5]) * 2
        assert 2 * steps <= e <= 2 * b[3] * steps
    assert w["notes"]["experts_routed"] == sum(w["experts_routed"])
    inp = adapter().layer_inputs(keep["state"], w)
    assert inp["routed_layers"] == 2 and inp["setup_weights_s"] > 0
    checks = adapter().check(keep["state"], w,
                             answer=adapter().control_answer(smoke.config))
    assert [n for n, v, lim in checks if v > lim]


# Each fault is planted through a `pytest.MonkeyPatch`, so that a script
# can plant the same faults at the cell's own size on the chip.

def plant_bias_on_gate_weights(mp):
    import jax
    import jax.numpy as jnp
    from repro.nn import moe
    orig = moe.route_sigmoid

    def biased(x, router_w, bias, top_k, scaling, norm_topk):
        _, idx = orig(x, router_w, bias, top_k, scaling, norm_topk)
        s = jax.nn.sigmoid(x.astype(jnp.float32) @ router_w) + bias
        w = jnp.take_along_axis(s, idx, axis=-1)
        return w / jnp.sum(w, -1, keepdims=True) * scaling, idx
    mp.setattr(moe, "route_sigmoid", biased)


def plant_shared_experts_skipped(mp):
    import jax.numpy as jnp
    from repro.nn import moe
    orig = moe.routed_apply

    def unshared(params, x, **kw):
        sh = dict(params["shared_mlp"],
                  wo=jnp.zeros_like(params["shared_mlp"]["wo"]))
        return orig(dict(params, shared_mlp=sh), x, **kw)
    mp.setattr(moe, "routed_apply", unshared)


def plant_latent_rmsnorm_skipped(mp):
    from repro.nn import attention, core
    shim = types.SimpleNamespace(**vars(core))
    shim.rmsnorm_apply = lambda params, x, eps=1e-6: x
    mp.setattr(attention, "core", shim)


def plant_rope_key_one_position_off(mp):
    from repro.nn import attention
    orig = attention.mla_latent

    def shifted(params, h, positions, theta, eps):
        return orig(params, h, positions + 1, theta, eps)
    mp.setattr(attention, "mla_latent", shifted)


def plant_latent_cache_write_skipped(mp):
    """`mla_decode` attends with the row's own latent but hands back the
    cache it was given: no position's latent or rope key is kept."""
    from repro.nn import attention
    orig = attention.mla_decode

    def stale(params, h, c_cache, pe_cache, *a, **kw):
        out, _, _ = orig(params, h, c_cache, pe_cache, *a, **kw)
        return out, c_cache, pe_cache
    mp.setattr(attention, "mla_decode", stale)


def plant_half_of_the_batch_left_out(mp):
    """Each step computes the first half of the rows and copies them
    over the rest, latent cache rows included."""
    from repro.models import transformer
    orig = transformer.decode_step

    def rows(cache, ix):
        return {k: v[:, ix] if k in ("ckv", "kpe") else v
                for k, v in cache.items()}

    def half(params, cfg, token, cache, cur_len, **kw):
        b = token.shape[0]
        h = max(1, b // 2)
        idx = np.arange(b) % h
        logits, c = orig(params, cfg, token[:h], rows(cache, slice(0, h)),
                         cur_len, **kw)
        return logits[idx], rows(c, idx)
    mp.setattr(transformer, "decode_step", half)


FAULTS = {
    "bias_on_gate_weights": plant_bias_on_gate_weights,
    "shared_experts_skipped": plant_shared_experts_skipped,
    "latent_rmsnorm_skipped": plant_latent_rmsnorm_skipped,
    "rope_key_one_position_off": plant_rope_key_one_position_off,
    "latent_cache_write_skipped": plant_latent_cache_write_skipped,
    "half_of_the_batch_left_out": plant_half_of_the_batch_left_out,
}


def _fails(monkeypatch, smoke, fault):
    FAULTS[fault](monkeypatch)
    line = run(smoke)
    assert not line["correct"] and line["failed"] == 0
    assert "logit_gap_mean" in failed_checks(line)


def test_bias_applied_to_the_gate_weights(monkeypatch, smoke):
    _fails(monkeypatch, smoke, "bias_on_gate_weights")


def test_shared_experts_skipped(monkeypatch, smoke):
    _fails(monkeypatch, smoke, "shared_experts_skipped")


def test_latent_rmsnorm_skipped(monkeypatch, smoke):
    _fails(monkeypatch, smoke, "latent_rmsnorm_skipped")


def test_rope_key_one_position_off_in_decode(monkeypatch, smoke):
    _fails(monkeypatch, smoke, "rope_key_one_position_off")


def test_latent_cache_write_skipped(monkeypatch, smoke):
    _fails(monkeypatch, smoke, "latent_cache_write_skipped")


def test_half_of_the_batch_left_out(monkeypatch, smoke):
    _fails(monkeypatch, smoke, "half_of_the_batch_left_out")


def test_layer_0_run_as_moe(monkeypatch, smoke):
    """A program whose first layer is routed: its own layout is not the
    file's, so the weights made from the file do not fit it and the run
    ends without a result; the drift checks name it."""
    from repro.models import registry, transformer
    orig = registry.get
    monkeypatch.setattr(registry, "get", lambda arch, smoke=False: (
        dataclasses.replace(orig(arch, True)[0], first_k_dense=0),
        transformer))
    out = io.StringIO()
    with pytest.raises(ValueError):
        runner.run(smoke, seed=5, seconds=1.0, traced=False, platform="cpu",
                   out=out, err=io.StringIO())
    assert out.getvalue() == ""
    serve = cells.module("adapters", "serve")
    pcfg, _ = registry.get(ARCH)
    import jax.numpy as jnp
    m = smoke.config["model"]
    params = latent_moe_weights.make_fn(m, smoke.config["weights"],
                                        jnp.float32)(latent_moe_weights.key(5))
    assert serve.model_drift(pcfg, m) == 1
    assert serve.layout_drift(transformer, pcfg, params) > 0


def test_readings_count_the_tokens_the_reference_did_not_put_first():
    ref = [np.array([[1.0, 4.0, -2.0], [0.5, -8.0, 3.0]], np.float32)]
    r = adapter().readings(ref, [[1, 2]])
    assert r["argmax_miss_share"] == 0.0 and r["logit_gap"] == 0.0
    r = adapter().readings(ref, [[0, 2]])
    assert r["argmax_miss_share"] == 0.5
    assert r["logit_gap_mean"] == pytest.approx(3.0 / 8.0)


def test_model_field_that_differs_from_the_file(smoke):
    smoke.config["model"] = dict(smoke.config["model"], routed_scaling=1.0)
    line = run(smoke)
    assert not line["correct"]
    assert "config_drift" in failed_checks(line)


# ---------------------------------------------------------------------------
# the reference and the weights
# ---------------------------------------------------------------------------

W = {"embed_std": 0.1, "norm_scale_std": 0.1, "router_bias_std": 0.1}


def test_reference_matches_the_programs_forward_at_float32():
    import jax
    import jax.numpy as jnp
    from repro.models import registry, transformer
    cfg, _ = registry.get(ARCH, smoke=True)
    m = _record(cfg)
    params = latent_moe_weights.make_fn(m, W, jnp.float32)(
        latent_moe_weights.key(5))
    toks = np.random.default_rng(5).integers(2, m["vocab"], (2, 20))
    with jax.default_matmul_precision("highest"):
        h, _ = transformer.forward(params, cfg, jnp.asarray(toks),
                                   remat=False)
        want = np.asarray(transformer.unembed(params, h))
        got = ref_latent_moe.Reference(m, cfg.norm_eps).logits(
            params, list(toks), [np.arange(20)] * 2)
    for g, x in zip(got, want):
        assert np.abs(g - x).max() <= 1e-4 * np.abs(x).max()


def test_weights_follow_the_seed_and_the_programs_layout():
    import jax
    import jax.numpy as jnp
    from repro.models import registry, transformer
    cfg, _ = registry.get(ARCH, smoke=True)
    m = _record(cfg)
    make = latent_moe_weights.make_fn(m, W, jnp.bfloat16)
    a, b = make(latent_moe_weights.key(2**33 + 1)), \
        make(latent_moe_weights.key(2**33 + 1))
    c = make(latent_moe_weights.key(7))
    scfg = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)
    serve = cells.module("adapters", "serve")
    assert serve.layout_drift(transformer, scfg, a) == 0
    moe = a["layers"]["moe"]
    assert moe["router"].dtype == moe["router_bias"].dtype == jnp.float32
    assert moe["wi"].shape == (2, 8, 64, 24)
    assert a["lm_head"].dtype == jnp.bfloat16
    assert float(jnp.std(moe["router_bias"])) > 0.05
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(x, np.float32),
                              np.asarray(y, np.float32))
    assert not np.array_equal(np.asarray(a["lm_head"], np.float32),
                              np.asarray(c["lm_head"], np.float32))


# ---------------------------------------------------------------------------
# the step's work and the readers
# ---------------------------------------------------------------------------

SHAPE = dict(n_layers=3, first_k_dense=1, d_model=8, n_heads=2,
             kv_lora_rank=4, qk_nope_head_dim=2, qk_rope_head_dim=2,
             v_head_dim=2, d_ff=16, moe_d_ff=4, n_experts=8,
             n_shared_experts=1, top_k=2, vocab=32)


def test_latent_moe_step_work_by_hand():
    w = cells.module("work", "latent_moe_step").work(
        batch=3, attended=5, experts_read=2.5, param_bytes=2,
        router_bytes=4, cache_bytes=4, logit_bytes=2, **SHAPE)
    # attention a layer: wq 8x2x4 = 64, wkva 8x6 = 48, wkvb 4x2x4 = 32,
    # wo 2x2x8 = 32 -> 176; dense MLP 3x8x16 = 384; an expert 3x8x4 = 96;
    # router 8x8 = 64; head 8x32 = 256
    per_token = 3 * 176 + 384 + 2 * (64 + 96 + 2 * 96) + 256
    attn = 2 * 3 * 3 * 2 * (2 * 4 + 2) * 5
    assert w["flops"] == 2 * 3 * per_token + attn
    norms = 3 * (16 + 4) + 8
    weights = (3 * 176 + 384 + 2 * (96 + 2.5 * 96) + norms + 3 * 8
               + 256) * 2 + 2 * (64 + 8) * 4
    cache = 3 * 3 * 6 * 4 * 6
    assert w["bytes"] == weights + cache + 3 * 32 * 2


def _ctx():
    """Two `run()` calls, the second inside the trace: 2 rows, a 3-token
    prompt and 2 decode steps, 40 distinct experts over its 5 steps and 2
    routed layers; device busy 4 of its 10 ns; two requests due before
    the trace, which waited 200 and 100 ms."""
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
    return {"cell": cells.Cell("x", 1, {}, {}, [], [], BENCH),
            "trace": trace.reduce(ev),
            "spans": [("run", 0.3, 1.0), ("run", 2.0, 2.5)],
            "traced_from_s": 1.5, "traced_until_s": 3.0,
            "peaks": cells.peaks("TPU v5 lite"),
            "inputs": {"m": m, "param_bytes": 2, "cache_bytes": 4,
                       "logit_bytes": 2, "setup_compile_s": 1.25,
                       "setup_weights_s": 0.5, "routed_layers": 2},
            "window": {"done": [{"req": {"due": 0.1}, "wait_s": 0.2},
                                {"req": {"due": 0.2}, "wait_s": 0.1},
                                {"req": {"due": 1.9}, "wait_s": 0.1}],
                       "experts_routed": [20, 40],
                       "batches": [(0.3, 1.0, 2, 2, 3, 2),
                                   (2.0, 2.5, 2, 2, 3, 2)]}}


def _read(name, ctx):
    return cells.module("metrics", name).read(ctx)


def test_moe_readers_on_a_synthetic_trace():
    ctx = _ctx()
    st = moe_steps.traced_steps(ctx)
    assert st == [(2, 1, 4.0), (2, 2, 4.0), (2, 3, 4.0), (2, 4, 4.0),
                  (2, 5, 4.0)]
    assert _read("experts_routed_per_step.moe", ctx) == pytest.approx(
        60 / (10 * 2))
    # the engine's readers, which the cell lists too
    assert _read("step_device_ms.serve", ctx) == pytest.approx(
        3e-9 * 1e3 / 5)
    assert _read("host_ms_per_step.serve", ctx) == pytest.approx(
        (10e-9 - 4e-9) * 1e3 / 5)
    assert _read("device_idle_share.serve", ctx) == pytest.approx(96.0)
    assert _read("setup_weights_s.serve", ctx) == 0.5
    assert _read("setup_compile_s.serve", ctx) == 1.25
    assert _read("batch_items_mean.serve", ctx) == 2.0
    # waits of the requests due before the trace: 200 and 100 ms
    assert _read("queue_wait_ms_p95.serve", ctx) == pytest.approx(195.0)
    p = ctx["peaks"]
    ws = [moe_steps.step_work(ctx, r, a, e) for r, a, e in st]
    least = sum(max(w["flops"] / p["bf16_flops_per_s"],
                    w["bytes"] / p["hbm_bytes_per_s"]) for w in ws)
    assert _read("step_roofline.moe", ctx) == pytest.approx(
        100 * least / 3e-9)
    assert _read("model_mfu.moe", ctx) == pytest.approx(
        100 * sum(w["flops"] for w in ws) / (100e-9 * p["bf16_flops_per_s"]))


def test_moe_readers_find_nothing_without_a_counter_or_a_trace():
    ctx = _ctx()
    del ctx["window"]["experts_routed"]
    for name in ("experts_routed_per_step.moe", "step_roofline.moe",
                 "model_mfu.moe"):
        assert _read(name, ctx) is None
    ctx = _ctx()
    ctx["window"]["batches"] = ctx["window"]["batches"][:1]
    ctx["window"]["experts_routed"] = [20]
    ctx["spans"] = ctx["spans"][:1]
    for name in ("step_device_ms.serve", "host_ms_per_step.serve",
                 "step_roofline.moe", "model_mfu.moe"):
        assert _read(name, ctx) is None
