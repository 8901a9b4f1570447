"""Users of one Monte Carlo draw, from the draw's key and the
population record of a configuration file.

Archetype and timezone are categorical draws and the climate offset
and battery fade uniform draws in each archetype's range, each from its
own split of the key: the sampler of the fleet model, kept here so the
reference rebuilds the draw's users without the program's code.
"""
from __future__ import annotations

import numpy as np


def sample(pop_spec: dict, n: int, key) -> dict:
    import jax
    import jax.numpy as jnp
    archs = pop_spec["archetypes"]
    w = np.asarray([a["weight"] for a in archs], np.float64)
    w = w / w.sum()
    tz_h = np.asarray(pop_spec["tz_hours"], np.float64)
    tw = np.asarray(pop_spec.get("tz_weights")
                    or [1.0] * len(tz_h), np.float64)
    tw = tw / tw.sum()
    k_arch, k_tz, k_amb, k_fade = jax.random.split(key, 4)
    arch = np.asarray(jax.random.choice(k_arch, len(archs), (n,),
                                        p=jnp.asarray(w)), np.int32)
    tz_idx = np.asarray(jax.random.choice(k_tz, len(tz_h), (n,),
                                          p=jnp.asarray(tw)), np.int64)
    lo = np.asarray([a["ambient_offset_c"][0] for a in archs])
    hi = np.asarray([a["ambient_offset_c"][1] for a in archs])
    u = np.asarray(jax.random.uniform(k_amb, (n,)), np.float64)
    flo = np.asarray([a["fade"][0] for a in archs])
    fhi = np.asarray([a["fade"][1] for a in archs])
    v = np.asarray(jax.random.uniform(k_fade, (n,)), np.float64)
    return {"archetype": arch, "tz_hours": tz_h[tz_idx],
            "ambient_offset_c": lo[arch] + u * (hi - lo)[arch],
            "fade": flo[arch] + v * (fhi - flo)[arch]}
