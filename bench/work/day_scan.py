"""Work of the day scan, from the shapes the problem defines.

Per combo and step the battery/thermal/throttle equations of both
nodes (glasses and pocket host) and the day summary: 60 operations
for the two battery and thermal updates, 43 for the hysteresis
triggers, shutdown latch, load and pods, 7 for the summary (death
time, peak, sums) -- 110 in all, each `exp` counted as one.  Bytes:
each combo's step tables read once (3 level tables of L float32 and 5
per-step float32 columns) and its 12-number summary written once.
"""

OPS_PER_COMBO_STEP = 110


def work(combos: int, steps: int, levels: int) -> dict:
    flops = OPS_PER_COMBO_STEP * combos * steps
    nbytes = combos * (steps * (3 * levels + 5) * 4 + 12 * 4)
    return {"flops": float(flops), "bytes": float(nbytes)}
