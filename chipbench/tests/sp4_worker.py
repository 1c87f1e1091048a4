"""Run by ``test_sp4.py`` in a process of its own with four simulated CPU
devices: the four-chip cell at the SMOKE size, through ``run_cell`` past
the harness's look for chips, once as it is and once with half of the
batch left out of the program's loss (a fault ``test_faults.py`` plants).
Prints one JSON result per run."""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

CELL = "t2d-3b.train-sp4.16x1024"
SMALL = dict(batch=1, temporal=8, spatial=8)


def main():
    from chipbench.harness import Benchmark
    from chipbench.run import run_cell
    from repro.models import transformer2d
    bench = Benchmark(ROOT)
    traffic = dict(bench.traffic(bench.cell(CELL)["traffic"]), **SMALL)
    devices = jax.devices()[:4]
    whole = transformer2d.t2d_loss

    def half(params, batch, cfg, **kw):
        t = batch["x"].shape[1] // 2
        return whole(params, {k: (v[:, :t] if v.ndim > 1 else v)
                              for k, v in batch.items()}, cfg, **kw)

    for fault in (None, half):
        transformer2d.t2d_loss = fault or whole
        r = run_cell(bench, CELL, 2 ** 31 + 61, 0.2, 0, devices,
                     t_start=time.monotonic(), smoke=True, traffic=traffic)
        r["devices"] = [str(d) for d in devices]
        print(json.dumps(r), flush=True)


if __name__ == "__main__":
    main()
