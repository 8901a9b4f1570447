"""Fused day-integrator Pallas kernel: battery SoC + 2-node thermal RC +
throttle hysteresis, one step per clock tick across 128 combos per lane
block.

The XLA path integrates the day as a `jax.lax.scan` over a `jax.vmap`
batch (`daysim._integrate_one`), which materializes every per-step
intermediate between scan iterations.  This kernel keeps the whole
9-variable integrator state — glasses/puck SoC, four RC node
temperatures, the two hysteresis latches and the shutdown latch — in a
(9, 128) VMEM scratch tile and walks time chunks sequentially (the last
grid dimension), so one combo's entire day never leaves vector
registers + VMEM.  Combos ride the 128-wide lane dimension; the
per-(time, level) power/pods tables stream in as (chunk, L, 128)
blocks and throttle-level selection is a hat-weight gather
(`max(1 - |level - l|, 0)`), exact at the integer levels the hard
hysteresis comparisons produce — forward dynamics are bit-compatible
with `daysim._step_math`, whose STE comparisons also forward the hard
values.

`day_scan(tables)` accepts the same batched table pytree the vmapped
scan consumes ((N, T, L) level tables, (N, T) step rows, (N,) consts)
and returns the output subset the day summarizer needs.  Through
`kernels.ops` it runs in interpret mode on the CPU (tests) and compiled
everywhere else (tests/test_tpu_compile.py compiles it for a v5e);
`day_scan_ref` is the `_integrate_one` oracle restricted to the same
outputs — parity is asserted at 1e-6 in tests/test_kernels.py,
throttling and puck-split combos included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128                     # combos per lane block

# integrator state rows in the VMEM scratch tile
_STATE = ("soc", "soc_p", "t_soc", "t_skin", "t_soc_p", "t_skin_p",
          "th_state", "soc_state", "shut")
# outputs (in kernel out_specs order); the subset `_summarize_jax` reads
OUTS = ("soc", "soc_p", "t_skin", "t_skin_p", "shut", "level", "pods",
        "drain_mw", "drain_p_mw")


def _day_kernel(mw_ref, mwp_ref, pods_ref, amult_ref, amb_ref, act_ref,
                val_ref, chg_ref, chgp_ref, const_ref,
                soc_o, socp_o, tskin_o, tskinp_o, shut_o, level_o,
                pods_o, drain_o, drainp_o, state, *, chunk: int,
                n_lvl: int, cidx: dict):
    tc = pl.program_id(1)

    def c(name):
        return const_ref[cidx[name], :]

    @pl.when(tc == 0)
    def _init():
        amb0 = amb_ref[0, :]
        one = jnp.ones_like(amb0)
        zero = jnp.zeros_like(amb0)
        for row, v in enumerate((one, one, amb0, amb0, amb0, amb0,
                                 zero, zero, zero)):
            state[row, :] = v

    amult = amult_ref[...]              # (L, LANES)
    # Mosaic builds integer iotas only
    lvls = jax.lax.broadcasted_iota(jnp.int32, (n_lvl, LANES),
                                    0).astype(jnp.float32)

    def take(tab, level):
        """Hat-weight level gather — exact at integer levels."""
        w = jnp.maximum(1.0 - jnp.abs(level[None, :] - lvls), 0.0)
        return jnp.sum(tab * w, axis=0)

    def node_step(pre, soc, t_soc, t_skin, p_mw, charge_mw, amb):
        # keep the op order in lockstep with daysim._node_step
        v = (c(pre + "v_full") - c(pre + "sag_v") * (1.0 - soc)
             - c(pre + "knee_v") * jnp.exp(-c(pre + "knee_sharp") * soc))
        i_a = p_mw * 1e-3 / v
        loss_mw = i_a * i_a * c(pre + "r_ohm") * 1e3
        drain_mw = p_mw + loss_mw
        soc_n = jnp.minimum(jnp.maximum(
            soc - drain_mw * c(pre + "dsoc_coeff")
            + charge_mw * c(pre + "dsoc_coeff"), 0.0), 1.0)
        heat_w = drain_mw * 1e-3
        flow = (t_soc - t_skin) * c(pre + "g_soc_skin")
        t_soc_n = t_soc + (heat_w - flow) * c(pre + "dt_c_soc")
        t_skin_n = t_skin + (flow - (t_skin - amb)
                             * c(pre + "g_skin_amb")) \
            * c(pre + "dt_c_skin")
        return soc_n, t_soc_n, t_skin_n, drain_mw

    def step(i, carry):
        (soc, soc_p, t_soc, t_skin, t_soc_p, t_skin_p,
         th_state, soc_state, shut) = carry
        # hysteresis triggers on the previous step's state (hard
        # comparisons — the forward values of daysim's STE surrogates)
        trip_t = jnp.where(t_skin > c("temp_trip"), 1.0, 0.0)
        clear_t = jnp.where(t_skin < c("temp_clear"), 1.0, 0.0)
        th_state = trip_t + (1.0 - trip_t) * (1.0 - clear_t) * th_state
        soc_eff = jnp.minimum(soc, soc_p)
        trip_s = jnp.where(soc_eff < c("soc_trip"), 1.0, 0.0)
        clear_s = jnp.where(soc_eff > c("soc_clear"), 1.0, 0.0)
        soc_state = trip_s + (1.0 - trip_s) * (1.0 - clear_s) * soc_state
        level = jnp.minimum(th_state + soc_state, c("max_level"))

        shut = jnp.maximum(shut, jnp.where(t_skin > c("shutdown_c"),
                                           1.0, 0.0))
        shut = jnp.maximum(
            shut, jnp.where(t_skin_p > c("shutdown_c"), 1.0, 0.0)
            * c("has_puck"))

        alive = (jnp.where(soc > 0.0, 1.0, 0.0)
                 * jnp.where(soc_p > 0.0, 1.0, 0.0)
                 * (1.0 - shut) * val_ref[i, :])
        act = act_ref[i, :] * take(amult, level)
        p_mw = (act * take(mw_ref[i], level)
                + (1.0 - act) * c("standby_mw")) * alive
        p_p_mw = (act * take(mwp_ref[i], level)
                  + (1.0 - act) * c("p_standby_mw")) * alive \
            * c("has_puck")

        amb = amb_ref[i, :]
        soc, t_soc, t_skin, drain_mw = node_step(
            "", soc, t_soc, t_skin, p_mw, chg_ref[i, :], amb)
        soc_p, t_soc_p, t_skin_p, drain_p_mw = node_step(
            "p_", soc_p, t_soc_p, t_skin_p, p_p_mw, chgp_ref[i, :], amb)

        soc_o[i, :] = soc
        socp_o[i, :] = soc_p
        tskin_o[i, :] = t_skin
        tskinp_o[i, :] = t_skin_p
        shut_o[i, :] = shut
        level_o[i, :] = level
        pods_o[i, :] = act * take(pods_ref[i], level) * alive
        drain_o[i, :] = drain_mw
        drainp_o[i, :] = drain_p_mw
        return (soc, soc_p, t_soc, t_skin, t_soc_p, t_skin_p,
                th_state, soc_state, shut)

    carry = tuple(state[row, :] for row in range(len(_STATE)))
    carry = jax.lax.fori_loop(0, chunk, step, carry)
    for row, v in enumerate(carry):
        state[row, :] = v


def day_scan(tables: dict, *, chunk: int = 128, interpret: bool) -> dict:
    """Integrate the batched day tables through the fused Pallas step.

    `tables` is the `daysim.batch_tables`-shaped pytree ((N, T, L) level
    tables, (N, T) step rows, (N, L) act_mult, const dict of (N,)
    scalars).  Returns {out: (N, T)} for `OUTS` (level as int32),
    matching `day_scan_ref` / the vmapped `_integrate_one` outputs.
    `kernels.ops.day_scan` picks `interpret` for the running backend."""
    mw = jnp.asarray(tables["step_mw"], jnp.float32)
    n, t, n_lvl = mw.shape
    nb = -(-n // LANES)
    n_pad = nb * LANES
    nc = -(-t // chunk)
    t_pad = nc * chunk

    def tln(x):                         # (N, T, L) -> (Tp, L, Np)
        x = jnp.moveaxis(jnp.asarray(x, jnp.float32), 0, -1)
        return jnp.pad(x, ((0, t_pad - t), (0, 0), (0, n_pad - n)),
                       mode="edge")

    def tn(x):                          # (N, T) -> (Tp, Np)
        x = jnp.asarray(x, jnp.float32).T
        return jnp.pad(x, ((0, t_pad - t), (0, n_pad - n)), mode="edge")

    ckeys = tuple(sorted(tables["const"]))
    cidx = {k: i for i, k in enumerate(ckeys)}
    cmat = jnp.pad(
        jnp.stack([jnp.asarray(tables["const"][k], jnp.float32)
                   for k in ckeys]),
        ((0, 0), (0, n_pad - n)), mode="edge")          # (C, Np)
    amult = jnp.pad(jnp.asarray(tables["act_mult"], jnp.float32).T,
                    ((0, 0), (0, n_pad - n)), mode="edge")  # (L, Np)
    # valid pads with zeros along time (the day is over), edge over lanes
    valid = jnp.pad(jnp.asarray(tables["valid"], jnp.float32).T,
                    ((0, t_pad - t), (0, 0)), mode="constant")
    valid = jnp.pad(valid, ((0, 0), (0, n_pad - n)), mode="edge")

    kernel = functools.partial(_day_kernel, chunk=chunk, n_lvl=n_lvl,
                               cidx=cidx)
    tl_spec = pl.BlockSpec((chunk, n_lvl, LANES),
                           lambda bi, tc: (tc, 0, bi))
    tn_spec = pl.BlockSpec((chunk, LANES), lambda bi, tc: (tc, bi))
    outs = pl.pallas_call(
        kernel,
        grid=(nb, nc),                  # time chunks sequential (last)
        in_specs=[
            tl_spec, tl_spec, tl_spec,
            pl.BlockSpec((n_lvl, LANES), lambda bi, tc: (0, bi)),
            tn_spec, tn_spec, tn_spec, tn_spec, tn_spec,
            pl.BlockSpec((len(ckeys), LANES), lambda bi, tc: (0, bi)),
        ],
        out_specs=[tn_spec] * len(OUTS),
        out_shape=[jax.ShapeDtypeStruct((t_pad, n_pad), jnp.float32)
                   for _ in OUTS],
        scratch_shapes=[pltpu.VMEM((len(_STATE), LANES), jnp.float32)],
        interpret=interpret,
    )(tln(tables["step_mw"]), tln(tables["step_mw_p"]),
      tln(tables["step_pods"]), amult, tn(tables["ambient"]),
      tn(tables["active"]), valid, tn(tables["charge"]),
      tn(tables["charge_p"]), cmat)
    ys = {k: o[:t, :n].T for k, o in zip(OUTS, outs)}
    ys["level"] = jnp.round(ys["level"]).astype(jnp.int32)
    return ys


def day_scan_ref(tables: dict) -> dict:
    """Oracle: the vmapped `daysim._integrate_one` scan restricted to
    the kernel's output set (the allclose target of the parity tests)."""
    from ..core import daysim
    ys = jax.vmap(daysim._integrate_one)(
        jax.tree_util.tree_map(jnp.asarray, tables))
    return {k: ys[k] for k in OUTS}
