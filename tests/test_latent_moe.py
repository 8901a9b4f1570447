"""moonlight-16b-a3b: its configuration, latent attention, the dropless
routed-expert layer and its router, against the plain reference
(`models/reference_latent_moe.py`) on seeded random weights at the smoke
size, on the CPU in float32."""
import dataclasses
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LatentMoEConfig, ModelConfig
from repro.models import reference_latent_moe as ref
from repro.models import registry, transformer
from repro.nn import attention, core, moe
from repro.serving.engine import Request, Server

ROOT = Path(__file__).resolve().parent.parent
ARCH = "moonlight-16b-a3b"
decode_step = jax.jit(transformer.decode_step, static_argnums=1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _weights(cfg, seed=0):
    """The program's layout with non-trivial norm scales and a non-zero
    router bias, so that neither can be dropped unseen."""
    params = transformer.init(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    key = jax.random.PRNGKey(seed + 1)
    out = []
    for i, (path, a) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        z = jax.random.normal(jax.random.fold_in(key, i), a.shape, a.dtype)
        if name.endswith("['scale']"):
            a = 1.0 + 0.1 * z
        elif name.endswith("['router_bias']"):
            a = 0.1 * z
        out.append(a)
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture(scope="module")
def smoke():
    cfg, _ = registry.get(ARCH, smoke=True)
    params = _weights(cfg)
    toks = np.random.default_rng(3).integers(2, cfg.vocab, (2, 14))
    want = np.stack([np.asarray(ref.logits(params, cfg, t)) for t in toks])
    return cfg, params, toks, want


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

def test_config_holds_the_published_numbers():
    """The catalog's config.json numbers (Moonlight-16B-A3B)."""
    cfg, model = registry.get(ARCH)
    assert model is transformer and isinstance(cfg, LatentMoEConfig)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.vocab) == \
        (27, 2048, 16, 163840)
    assert (cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.qk_nope_head_dim,
            cfg.v_head_dim) == (512, 64, 128, 128)
    assert (cfg.n_experts, cfg.moe_d_ff, cfg.top_k, cfg.n_shared_experts,
            cfg.first_k_dense, cfg.d_ff) == (64, 1408, 6, 2, 1, 11264)
    assert cfg.routed_scaling == 2.446 and cfg.norm_topk_prob
    assert (cfg.norm_eps, cfg.rope_theta) == (1e-5, 50000.0)
    params = jax.eval_shape(lambda: transformer.init(jax.random.PRNGKey(0),
                                                     cfg))
    assert params["lm_head"].shape == (2048, 163840)            # untied
    assert params["layers"]["moe"]["router_bias"].shape == (26, 64)
    assert params["dense_layers"]["mlp"]["wi"].shape == (1, 2048, 11264)
    assert core.count_params(params) == cfg.n_params == 15_960_110_208
    cut = dataclasses.replace(cfg, n_layers=9)
    assert cut.n_params == 5_432_847_360


def test_smoke_keeps_every_mechanism():
    cfg, _ = registry.get(ARCH, smoke=True)
    assert cfg.first_k_dense == 1 and cfg.n_layers - cfg.first_k_dense >= 2
    assert cfg.n_experts >= 8 and cfg.top_k == 2
    assert cfg.n_shared_experts == 1 and cfg.kv_lora_rank == 32
    assert (cfg.qk_rope_head_dim, cfg.qk_nope_head_dim, cfg.v_head_dim) == \
        (8, 16, 16)


def test_the_sheet_shape_keeps_its_place_outside_moonlight():
    """moonshot-v1-16b-a3b stays the assignment sheet's shape; the
    dry-run grid covers the sheet's ten and not moonlight-16b-a3b."""
    assert ARCH in registry.arch_names() and ARCH not in registry.SHEET
    assert len(registry.SHEET) == 10 and "moonshot-v1-16b-a3b" in \
        registry.SHEET
    sheet, _ = registry.get("moonshot-v1-16b-a3b")
    assert type(sheet) is ModelConfig and sheet.n_layers == 48


def test_granite_fields_and_tree_are_unchanged():
    """Granite's config has exactly the fields its served cell's file
    records, and its weight tree has no leaf of the latent layout."""
    cfg, _ = registry.get("granite-3-2b")
    rec = json.loads((ROOT / "bench/configs/granite_3_2b_serve.json")
                     .read_text())["model"]
    assert [f.name for f in dataclasses.fields(cfg)] == list(rec)
    tree = jax.eval_shape(lambda: transformer.init(jax.random.PRNGKey(0),
                                                   cfg))
    assert sorted(tree) == ["embed", "final_norm", "layers"]
    assert sorted(tree["layers"]) == ["attn", "mlp", "norm1", "norm2"]
    assert sorted(tree["layers"]["attn"]) == ["wk", "wo", "wq", "wv"]
    assert set(transformer.init_cache(cfg, 2, 4, jnp.float32)) == {"k", "v"}


# ---------------------------------------------------------------------------
# the system against the plain reference
# ---------------------------------------------------------------------------

def test_forward_matches_the_reference(smoke):
    cfg, params, toks, want = smoke
    with jax.default_matmul_precision("highest"):
        h, aux = transformer.forward(params, cfg, jnp.asarray(toks),
                                     remat=False)
        got = np.asarray(transformer.unembed(params, h))
    assert float(aux) == 0.0
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_decode_through_the_latent_cache_matches_the_reference(smoke):
    """Prefill by decode, one token a step through the latent cache,
    against the reference's full pass.  5e-4 absolute: float32 sums in
    another order (absorbed attention, sorted grouped experts) over three
    layers leave ~1e-6 on logits of size ~3; a position off, a missing
    norm or a dropped expert moves them by 1e-2 or more."""
    cfg, params, toks, want = smoke
    B, S = toks.shape
    cache = transformer.init_cache(cfg, B, S + 2, jnp.float32)
    got = []
    with jax.default_matmul_precision("highest"):
        for t in range(S):
            lg, cache = decode_step(params, cfg,
                                                jnp.asarray(toks[:, t]),
                                                cache, jnp.asarray(t))
            got.append(np.asarray(lg))
    assert np.abs(np.stack(got, 1) - want).max() < 5e-4
    assert cache["ckv"].shape == (3, B, S + 2, 32)
    assert cache["kpe"].shape == (3, B, S + 2, 8)
    assert int(cache["experts_routed"]) > 0


def test_prefill_then_decode_matches_the_reference(smoke):
    cfg, params, toks, want = smoke
    B, S = toks.shape
    P = S - 4
    with jax.default_matmul_precision("highest"):
        h, cache = transformer.prefill(params, cfg, jnp.asarray(toks[:, :P]),
                                       max_len=S)
        got = [np.asarray(transformer.unembed(params, h))]
        for t in range(P, S):
            lg, cache = decode_step(params, cfg,
                                                jnp.asarray(toks[:, t]),
                                                cache, jnp.asarray(t))
            got.append(np.asarray(lg))
    assert np.abs(np.stack(got, 1) - want[:, P - 1:]).max() < 5e-4


def test_absorbed_decode_equals_expanded(smoke):
    """`mla_decode` attends in the latent space; expanding K and V from
    the same cache gives the same output."""
    cfg, params, _, _ = smoke
    p = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    rng = np.random.default_rng(0)
    B, T, cur = 2, 9, 6
    h = jnp.asarray(rng.normal(size=(B, cfg.d_model)), jnp.float32)
    c0 = jnp.asarray(rng.normal(size=(B, T, cfg.kv_lora_rank)), jnp.float32)
    pe0 = jnp.asarray(rng.normal(size=(B, T, cfg.qk_rope_head_dim)),
                      jnp.float32)
    nope = cfg.qk_nope_head_dim
    with jax.default_matmul_precision("highest"):
        got, c, pe = attention.mla_decode(p, h, c0, pe0, cur, cfg.rope_theta,
                                          cfg.norm_eps, nope)
        pos = jnp.full((1, 1), cur)
        q_nope, q_pe = attention.mla_query(p, h[:, None], pos,
                                           cfg.rope_theta, nope)
        kv = jnp.einsum("btr,rhk->bthk", c, p["wkvb"])
        s = (jnp.einsum("bhk,bthk->bht", q_nope[:, 0], kv[..., :nope])
             + jnp.einsum("bhk,btk->bht", q_pe[:, 0], pe))
        s = s / np.sqrt(nope + cfg.qk_rope_head_dim)
        s = jnp.where(jnp.arange(T) <= cur, s, -jnp.inf)
        o = jnp.einsum("bht,bthk->bhk", jax.nn.softmax(s, -1), kv[..., nope:])
        want = jnp.einsum("bhk,hkd->bd", o, p["wo"])
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    assert np.array_equal(c[:, cur + 1:], c0[:, cur + 1:])
    assert not np.allclose(c[:, cur], c0[:, cur])


# ---------------------------------------------------------------------------
# the router and the routed layer
# ---------------------------------------------------------------------------

def _route(logits, bias, k=2, scaling=2.446, norm=True):
    """`route_sigmoid` with the identity as router."""
    x = jnp.asarray(logits, jnp.float32)
    return moe.route_sigmoid(x, jnp.eye(x.shape[1]), jnp.asarray(bias,
                             jnp.float32), k, scaling, norm)


def test_router_bias_changes_selection_but_not_the_weights():
    logits = [[2.0, 1.0, 0.0, -1.0]]
    w0, i0 = _route(logits, [0.0, 0.0, 0.0, 0.0])
    w1, i1 = _route(logits, [0.0, -5.0, 0.0, 3.0])
    assert sorted(np.asarray(i0)[0]) == [0, 1]
    assert sorted(np.asarray(i1)[0]) == [0, 3]
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits[0])))
    want = s[np.asarray(i1)[0]] / s[np.asarray(i1)[0]].sum() * 2.446
    np.testing.assert_allclose(np.asarray(w1)[0], want, rtol=1e-6)


def test_router_weights_are_normalised_sigmoid_scores_times_the_scale():
    rng = np.random.default_rng(1)
    logits = rng.normal(size=(16, 8))
    w, i = _route(logits, np.zeros(8), k=3)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.446, rtol=1e-6)
    s = 1.0 / (1.0 + np.exp(-logits))          # sigmoid, not softmax
    sel = np.take_along_axis(s, np.asarray(i), -1)
    np.testing.assert_allclose(np.asarray(w),
                               sel / sel.sum(-1, keepdims=True) * 2.446,
                               rtol=1e-5)
    soft = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    assert not np.allclose(np.asarray(w) / 2.446,
                           np.take_along_axis(soft, np.asarray(i), -1))
    raw, _ = _route(logits, np.zeros(8), k=3, norm=False)
    np.testing.assert_allclose(np.asarray(raw), sel * 2.446, rtol=1e-5)


def _layer_params(cfg, seed=0):
    return jax.tree.map(lambda a: a[0], _weights(cfg, seed)["layers"]["moe"])


@pytest.mark.parametrize("skew, shape", [("uniform", (3, 40)),
                                         ("one_expert", (3, 40)),
                                         ("uniform", (1, 3))])
def test_routed_layer_drops_no_token(smoke, skew, shape):
    """Every token gets all its experts, however skewed the routing: with
    a bias that sends every token to expert 5 the layer still equals the
    reference's per-expert loop; so does a row count (1 x 3 tokens x 2)
    that the grouped matmul pads to a multiple of 8."""
    cfg = smoke[0]
    p = _layer_params(cfg)
    if skew == "one_expert":
        p["router_bias"] = p["router_bias"].at[5].set(100.0)
    x = jnp.asarray(np.random.default_rng(2).normal(size=shape
                                                    + (cfg.d_model,)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, n = moe.routed_apply(p, x, top_k=cfg.top_k,
                                scaling=cfg.routed_scaling, norm_topk=True)
        want = jnp.stack([ref.routed_experts(p, xi, cfg) for xi in x])
        ids, _ = ref.route(x.reshape(-1, cfg.d_model), p["router"],
                           p["router_bias"], cfg)
    np.testing.assert_allclose(y, want, atol=1e-4, rtol=1e-4)
    assert int(n) == len(np.unique(np.asarray(ids)))
    if skew == "one_expert":
        assert np.all(np.any(np.asarray(ids) == 5, axis=-1))


def test_shared_experts_are_added_unweighted(smoke):
    cfg = smoke[0]
    p = _layer_params(cfg, 1)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 5,
                                                           cfg.d_model)),
                    jnp.float32)
    no_routed = dict(p, wo=jnp.zeros_like(p["wo"]))
    y, _ = moe.routed_apply(no_routed, x, top_k=cfg.top_k,
                            scaling=cfg.routed_scaling, norm_topk=True)
    np.testing.assert_allclose(y, core.mlp_apply(p["shared_mlp"], x),
                               atol=1e-6, rtol=1e-6)


def test_expert_counter_counts_distinct_experts_a_step(smoke):
    """A decode step adds, per routed layer, the distinct experts its rows
    selected: the same count a one-token prefill of those rows gives, and
    between top_k and rows * top_k a layer."""
    cfg, params, toks, _ = smoke
    B = toks.shape[0]
    first = jnp.asarray(toks[:, :1])
    cache = transformer.init_cache(cfg, B, 4, jnp.float32)
    _, cache = decode_step(params, cfg, first[:, 0], cache, jnp.asarray(0))
    _, pre = transformer.prefill(params, cfg, first, max_len=4)
    n = int(cache["experts_routed"])
    n_routed = cfg.n_layers - cfg.first_k_dense
    assert n == int(pre["experts_routed"])
    assert cfg.top_k * n_routed <= n <= B * cfg.top_k * n_routed
    _, cache = decode_step(params, cfg, jnp.asarray(toks[:, 1]), cache,
                           jnp.asarray(1))
    assert int(cache["experts_routed"]) >= n + cfg.top_k * n_routed


# ---------------------------------------------------------------------------
# through the serving engine
# ---------------------------------------------------------------------------

def test_server_serves_it_and_reads_the_counter_once_a_batch(smoke):
    cfg, params, toks, want = smoke
    srv = Server(cfg, transformer, params, batch_slots=2, max_len=16, eos=-1)
    for i, t in enumerate(toks):
        srv.submit(Request(i, np.asarray(t[:10], np.int32),
                           max_new_tokens=4))
    with jax.default_matmul_precision("highest"):
        out = srv.run()
    assert [len(r.out_tokens) for r in out] == [4, 4]
    # the first served token is the reference's argmax after the prompt
    assert [r.out_tokens[0] for r in out] == \
        [int(np.argmax(want[i, 9])) for i in range(2)]
    steps = 10 + srv.stats.decode_steps
    n_routed = cfg.n_layers - cfg.first_k_dense
    assert cfg.top_k * n_routed * steps <= srv.stats.experts_routed <= \
        2 * cfg.top_k * n_routed * steps
    g, gm = registry.get("granite-3-2b", smoke=True)
    gs = Server(g, gm, gm.init(jax.random.PRNGKey(0), g), batch_slots=1,
                max_len=6, eos=-1)
    gs.submit(Request(0, np.full(2, 2, np.int32), max_new_tokens=2))
    gs.run()
    assert gs.stats.experts_routed == 0
