"""Serving load benchmark: static vs continuous batching on one arrival
trace (the serving trajectory's first datapoint).

A worker subprocess simulates an N-device mesh (default 8, data=1 so the
model axis carries DSP sequence parallelism), builds one sharded
ServingEngine, and replays the SAME synthetic Poisson arrival trace through
both batching policies:

* **static**  — ``serving.scheduler.replay_static``: FIFO chunks of
  ``max_batch``, each chunk waits for its last arrival, prefills together,
  decodes in lockstep until its slowest row finishes.
* **continuous** — ``serving.scheduler.ContinuousScheduler``: per-request
  admission the moment a slot frees, per-step retirement, slot reuse.

Both arms run the same jitted prefill/decode cells (warmed up before
timing), the same greedy decode, the same wall clock — only the batching
policy differs, and the worker asserts their tokens are IDENTICAL before
reporting any numbers.  Decode budgets are deliberately heterogeneous
(uniform over [min, max]): lockstep waste and queue-wait are exactly what
continuous batching exists to remove.

A second trace targets the PAGED tier (``serving.scheduler.PagedScheduler``):
every prompt opens with the same shared system prefix and decode budgets are
long-tailed, the workload prefix caching + chunked prefill exist for.  The
same trace runs through the slot scheduler (re-prefills the shared prefix
every admission) and the paged scheduler (radix-tree hits skip it); the
worker asserts token parity against the static oracle and reports the
prefill-compute saving (prefix-hit tokens / prompt tokens) alongside p99
TTFT — the full run asserts the saving clears 30%.

Writes ``BENCH_serving.json`` at the repo root: per-arm throughput tok/s,
p50/p99 TTFT and TPOT, queue wait, slot occupancy, plus the ratios, and the
``prefix_trace`` block (slot vs paged + prefill savings).  Run standalone
(``python benchmarks/serving_load.py [--steps 2]``) or via
``benchmarks/run.py serving_load``.  ``--steps`` caps the decode budgets —
CI smokes the JSON schema (both traces) with ``--steps 2``.
"""
import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")

SUMMARY_KEYS = (            # the schema CI smoke-checks (don't rot silently)
    "throughput_tok_s", "ttft_p50_s", "ttft_p99_s", "tpot_p50_s",
    "tpot_p99_s", "queue_wait_p50_s", "queue_wait_p99_s", "slot_occupancy",
    "tokens_generated", "decode_steps", "slots_allocated", "elapsed_s",
)
PAGED_KEYS = (              # extra gauges only the paged arm populates
    "prefix_hit_rate", "prefix_hit_tokens", "prefill_chunk_steps",
    "blocks_in_use", "blocks_free", "peak_blocks_in_use",
)


def _worker(cfg: dict) -> None:
    """Runs inside the simulated-mesh subprocess; prints one JSON line."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.topology import Topology
    from repro.models.lm import LMConfig, init_lm
    from repro.parallel.partition import ParallelPlan
    from repro.serving.engine import Request, ServingEngine, _submesh
    from repro.serving.kv_pool import KVPool
    from repro.serving.scheduler import (ContinuousScheduler, PagedScheduler,
                                         replay_static)

    n_dev = cfg["devices"]
    max_batch = cfg["max_batch"]
    n_req = cfg["n_requests"]
    plen = cfg["prompt_len"]
    prefix_len = cfg.get("prefix_len", 0)
    block_size = cfg.get("block_size", 16)
    rng = np.random.RandomState(0)
    if cfg.get("tail") == "longtail":
        # long-tailed budgets: most requests finish fast, a few run long —
        # the regime where chunked prefill keeps the pool's decoders moving
        budgets = np.clip(cfg["min_new"]
                          + np.round(rng.exponential(6.0, n_req)).astype(int),
                          cfg["min_new"], cfg["max_new"])
    else:
        budgets = rng.randint(cfg["min_new"], cfg["max_new"] + 1, size=n_req)
    max_len = plen + int(budgets.max())
    max_len += (-max_len) % max(n_dev, 1)     # seq-sharded divisibility
    if cfg.get("paged"):
        max_len = int(max_len + (-max_len) % np.lcm(block_size,
                                                    max(n_dev, 1)))

    mcfg = LMConfig(name="bench-serve", n_layers=2, d_model=64, n_heads=8,
                    n_kv_heads=4, head_dim=16, d_ff=128, vocab=96,
                    dtype=jnp.float32)
    params = init_lm(jax.random.PRNGKey(0), mcfg)
    mesh = _submesh(n_dev, 1) if n_dev > 1 else None
    eng = ServingEngine(params, mcfg, max_len=max_len, mesh=mesh,
                        plan=ParallelPlan(mode="dsp" if mesh is not None
                                          else "none"),
                        topology=(Topology.flat_ici(n_dev)
                                  if n_dev > 1 else None))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (n_req, plen), 0,
                                 mcfg.vocab)
    if prefix_len:
        # every request opens with the SAME system prefix (the prefix-cache
        # workload); suffixes stay per-request random
        shared = jax.random.randint(jax.random.PRNGKey(2), (prefix_len,), 0,
                                    mcfg.vocab)
        prompts = jnp.concatenate(
            [jnp.broadcast_to(shared, (n_req, prefix_len)),
             prompts[:, prefix_len:]], axis=1)

    # -- warm every jit cache both arms will hit (compiles out of the timed
    # region: batch-1 + chunk prefill, pool + chunk decode) --------------------
    lg, caches1 = eng._prefill(prompts[:1])
    jax.block_until_ready(eng._decode(jnp.argmax(lg[:, -1], -1)[:, None],
                                      caches1))
    lgc, cachesc = eng._prefill(prompts[:max_batch])
    jax.block_until_ready(eng._decode(jnp.argmax(lgc[:, -1], -1)[:, None],
                                      cachesc))
    # a real KVPool so the warmed/calibrated decode signature (shapes AND
    # placement) is exactly the one the scheduler will run
    pool_caches = KVPool(mcfg, max_batch, max_len, mesh=mesh,
                         plan=eng.plan).caches
    tok = jnp.zeros((max_batch, 1), jnp.int32)
    jax.block_until_ready(eng._decode(tok, pool_caches)[0])

    # -- calibrate the arrival trace to the measured decode step (the pool's
    # REAL signature: per-slot pos, mesh placement) ---------------------------
    t0 = time.monotonic()
    reps = 10
    for _ in range(reps):
        lg, pool_caches = eng._decode(tok, pool_caches)
        jax.block_until_ready(lg)
    t_step = (time.monotonic() - t0) / reps
    mean_gap = cfg["gap_steps"] * t_step
    arrivals = np.cumsum(rng.exponential(mean_gap, size=n_req))
    arrivals[0] = 0.0

    def make_requests():
        return [Request(prompt=prompts[i], max_new_tokens=int(budgets[i]),
                        arrival_time=float(arrivals[i]), request_id=i)
                for i in range(n_req)]

    static_reqs, static_metrics = replay_static(eng, make_requests(),
                                                max_batch=max_batch)
    cont_reqs = make_requests()
    sched = ContinuousScheduler(eng, max_batch=max_batch)
    sched.run(cont_reqs)
    if mesh is not None:
        sched.pool.assert_on_mesh()

    by_id = {r.request_id: r for r in static_reqs}
    parity = all(by_id[r.request_id].generated == r.generated
                 for r in cont_reqs)
    assert parity, "continuous tokens diverged from the static oracle"

    out = {
        "config": {**cfg, "max_len": max_len, "t_step_s": t_step,
                   "budgets": budgets.tolist(),
                   "arrivals_s": np.round(arrivals, 4).tolist()},
        "parity": parity,
        "static": static_metrics.summary(),
        "continuous": sched.metrics.summary(),
    }

    if cfg.get("paged"):
        chunk = cfg.get("prefill_chunk", block_size)
        # warm the paged jit caches (chunk cell per width + block-layout
        # decode) on a throwaway scheduler so compiles stay out of the
        # timed trace, mirroring the slot arms' warmup above
        warm = [Request(prompt=prompts[i], max_new_tokens=2, request_id=i)
                for i in range(min(2, n_req))]
        PagedScheduler(eng, max_batch=max_batch, block_size=block_size,
                       prefill_chunk=chunk).run(warm)

        paged_reqs = make_requests()
        psched = PagedScheduler(eng, max_batch=max_batch,
                                block_size=block_size, prefill_chunk=chunk)
        psched.run(paged_reqs)
        if mesh is not None:
            psched.pool.assert_on_mesh()
        assert all(by_id[r.request_id].generated == r.generated
                   for r in paged_reqs), (
            "paged tokens diverged from the static oracle")
        ps = psched.metrics.summary()
        out["paged"] = ps
        # prefill compute ~ tokens pushed through the prefill/chunk cells:
        # the slot arm recomputes every prompt token, the paged arm skips
        # the radix-tree hits
        out["prefill"] = {
            "slot_prefill_tokens": n_req * plen,
            "paged_prefill_tokens": n_req * plen - ps["prefix_hit_tokens"],
            "saved_frac": ps["prefix_hit_tokens"] / float(n_req * plen),
        }
    print(json.dumps(out))


def run_trace(devices: int, *, n_requests=16, max_batch=4, prompt_len=16,
              min_new=2, max_new=32, gap_steps=1.5, **extra) -> dict:
    """Heterogeneous budgets (uniform [min_new, max_new]) are the point:
    static batching decodes every chunk to its SLOWEST row while continuous
    retires and refills per step — the gap is the lockstep waste.  ``extra``
    passes the prefix-trace knobs through to the worker (``prefix_len``,
    ``block_size``, ``prefill_chunk``, ``paged``, ``tail``)."""
    cfg = dict(devices=devices, n_requests=n_requests, max_batch=max_batch,
               prompt_len=prompt_len, min_new=min_new, max_new=max_new,
               gap_steps=gap_steps, **extra)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"     # a CPU simulation, never the chip
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--run-worker",
         json.dumps(cfg)],
        env=env, capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(f"serving_load worker failed:\n"
                           f"{proc.stderr[-3000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main(argv=None):
    if ROOT not in sys.path:        # standalone `python benchmarks/...` runs
        sys.path.insert(0, ROOT)
    from benchmarks.common import emit

    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=0,
                    help="cap decode budgets at this many tokens "
                    "(smoke mode; 0 = full trace)")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--out", default=os.path.join(ROOT,
                                                  "BENCH_serving.json"))
    args = ap.parse_args([] if argv is None else argv)

    smoke = 0 < args.steps < 8
    kw = {}
    pkw = dict(n_requests=16, max_batch=4, prompt_len=48, prefix_len=32,
               min_new=2, max_new=32, block_size=16, prefill_chunk=16,
               paged=True, tail="longtail")
    if smoke:
        kw = dict(n_requests=4, max_batch=2, min_new=max(args.steps, 2),
                  max_new=max(args.steps, 2))
        pkw.update(n_requests=3, max_batch=2, prompt_len=32, prefix_len=16,
                   min_new=max(args.steps, 2), max_new=max(args.steps, 2))
    elif args.steps:
        kw = dict(max_new=args.steps)
        pkw.update(max_new=args.steps)
    res = run_trace(args.devices, **kw)
    pres = run_trace(args.devices, **pkw)

    st, ct = res["static"], res["continuous"]
    pg = pres["paged"]
    for arm, s in (("static", st), ("continuous", ct),
                   ("prefix/slot", pres["continuous"]), ("prefix/paged", pg)):
        missing = [k for k in SUMMARY_KEYS if k not in s]
        assert not missing, f"{arm} summary lost keys: {missing}"
    missing = [k for k in PAGED_KEYS if k not in pg]
    assert not missing, f"paged summary lost keys: {missing}"
    res["ratios"] = {
        "throughput_x": (ct["throughput_tok_s"] / st["throughput_tok_s"]
                         if st["throughput_tok_s"] else None),
        "ttft_p99_x": (st["ttft_p99_s"] / ct["ttft_p99_s"]
                       if ct["ttft_p99_s"] else None),
    }
    res["prefix_trace"] = {
        "config": pres["config"],
        "slot": pres["continuous"],
        "paged": pg,
        "prefill": pres["prefill"],
    }
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2, sort_keys=True)

    emit("serving_load.static",
         st["ttft_p99_s"] * 1e6 if st["ttft_p99_s"] else None,
         f"thru={st['throughput_tok_s']:.1f}tok/s "
         f"occ={st['slot_occupancy']:.2f}")
    emit("serving_load.continuous",
         ct["ttft_p99_s"] * 1e6 if ct["ttft_p99_s"] else None,
         f"thru={ct['throughput_tok_s']:.1f}tok/s "
         f"occ={ct['slot_occupancy']:.2f}")
    emit("serving_load.ratio", None,
         f"thru_x={res['ratios']['throughput_x']:.2f} "
         f"ttft_p99_x={res['ratios']['ttft_p99_x']:.2f}")
    saved = res["prefix_trace"]["prefill"]["saved_frac"]
    emit("serving_load.paged",
         pg["ttft_p99_s"] * 1e6 if pg["ttft_p99_s"] else None,
         f"thru={pg['throughput_tok_s']:.1f}tok/s "
         f"hit={pg['prefix_hit_rate'] or 0:.2f} "
         f"chunks={pg['prefill_chunk_steps']}")
    emit("serving_load.prefix_savings", None,
         f"prefill_saved={saved:.0%} "
         f"({res['prefix_trace']['prefill']['paged_prefill_tokens']}"
         f"/{res['prefix_trace']['prefill']['slot_prefill_tokens']} tok)")

    if not smoke:
        assert ct["throughput_tok_s"] > st["throughput_tok_s"], (
            "continuous batching must beat static throughput", res["ratios"])
        assert ct["ttft_p99_s"] < st["ttft_p99_s"], (
            "continuous batching must beat static p99 TTFT", res["ratios"])
        assert saved >= 0.30, (
            "prefix cache must save >= 30% of prefill compute on the "
            "shared-prefix trace", res["prefix_trace"]["prefill"])
    print(f"# wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--run-worker":
        _worker(json.loads(sys.argv[2]))
    else:
        main(sys.argv[1:])
