"""Work of the fleet scan, from the shapes the problem defines.

Per user and step: the day step's 110 operations (see
`day_scan.py`), plus the per-stream load of 4 streams (gather, two
products and a live flag: 4 each) binned into the user's UTC hour (2
adds each for pods and live streams), 32 in all: 142.  Bytes: each
user's 20 per-user float32 inputs read once and its 7 outputs written
once; the (steps, archetypes) tables are read once per draw.
"""

OPS_PER_USER_STEP = 142


def work(users: int, steps: int, days: int, archetypes: int,
         levels: int, streams: int) -> dict:
    flops = OPS_PER_USER_STEP * users * steps * days
    tables = steps * archetypes * (levels * (3 + streams) + 5) * 4
    nbytes = users * (20 + 7) * 4 + tables
    return {"flops": float(flops), "bytes": float(nbytes)}
