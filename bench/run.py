#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration, traffic mix, limits and per-layer readers are files under
``bench/`` (see ``bench/harness.py``). The run makes the weights on the
device from the seed, builds the paged engine through the program's own
construction steps, warms every program the window will run, then serves
the mix for ``--seconds`` by the benchmark's own clock. ``--trace 1``
profiles the window (at most its first ``trace_s`` seconds, from the mix)
and reports the per-layer metrics; ``--trace 0`` reports the end-to-end ones.
Once the window has closed, the served tokens of every finished request
are checked against the float32 reference that the configuration names
(``bench/correct.py``).

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), and ``checks`` last: each number compared with its limit,
which are also the last lines of standard error. Lines before it are
readings, not metrics. It exits non-zero, printing no result, when JAX finds
no TPU or fewer chips than the cell asks for.

  python3 bench/run.py --workload <name> --cpu-rehearsal

runs the same code on the CPU at the tiny sizes the files give under
``rehearsal``, with the Pallas kernels in interpret mode; it never reports a
device result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Optional  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
#: the longest stretch of the window a ``--trace 1`` run profiles, unless
#: the mix gives its own ``trace_s``
TRACE_MAX_S = 8.0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true", help="tiny sizes on the CPU")
    ap.add_argument(
        "--backend", default="pallas_interpret", choices=("pallas_interpret", "xla"),
        help="GEMM backend of a rehearsal (a chip run always serves on the Pallas kernels)",
    )
    return ap.parse_args(argv)


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


class HostEvents:
    """Counts the compiles and compile-cache loads JAX reports, and keeps the
    host seconds spent in cache loads and in garbage collection."""

    def __init__(self):
        import jax

        self.reset()
        self._gc_t0 = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        gc.callbacks.append(self._on_gc)

    def _on(self, event: str, seconds: float, **_):
        # JAX reports a backend compile around every program it needs,
        # also one that it then loads from the persistent cache
        if "backend_compile" in event:
            self.requests += 1
        elif "cache_retrieval" in event:
            self.loads += 1
            self.load_s += seconds

    def _on_gc(self, phase: str, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc_s += time.perf_counter() - self._gc_t0

    def host(self):
        return self.gc_s, self.load_s

    @property
    def compiles(self) -> int:
        return self.requests - self.loads

    def reset(self):
        self.requests = self.loads = 0
        self.load_s = self.gc_s = 0.0


_EVENTS: Optional[HostEvents] = None


def host_events() -> HostEvents:
    """The process's one listener (JAX keeps every listener it is given)."""
    global _EVENTS
    if _EVENTS is None:
        _EVENTS = HostEvents()
    _EVENTS.reset()
    return _EVENTS


class Tracer:
    """The profiler over the window's first ``max_s`` seconds, with the
    benchmark's spans on and each step synchronised, so that device time
    falls inside the step that launched it."""

    def __init__(self, loop, max_s: float, rehearsal: bool = False):
        self.loop = loop
        self.max_s = max_s
        self.rehearsal = rehearsal
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.active = False
        self.steps = (0, 0)

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the benchmark's own spans are enough
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.loop.annotate = self.loop.sync_each_step = True
        self.first = len(self.loop.steps)
        self.active = True

    def maybe_stop(self, loop, t_open: float):
        if self.active and loop.clock() - t_open >= self.max_s:
            self.stop(loop)

    def stop(self, loop):
        import jax

        if not self.active:
            return
        self.active = False
        loop.annotate = loop.sync_each_step = False
        jax.profiler.stop_trace()
        self.steps = (self.first, len(loop.steps))

    def reduce(self):
        """The device's reduction (``bench.trace``) and the program spans'
        (``bench.program_spans``) of the one trace, which then goes."""
        from bench import program_spans, trace

        try:
            ops, spans, program = program_spans.events_from_xplane(
                trace.find_xplane(self.dir), host_stands_in=self.rehearsal
            )
            return trace.reduce(ops, spans), program_spans.reduce(ops, spans, program)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def run_cell(
    cell, seed: int, seconds: float, *, traced: bool, backend: str, pk: Dict, control: bool = False,
    rehearsal: bool = False,
) -> Dict:
    """Set up, serve the window, check the served tokens. Returns the
    numbers the result line and the readings are made of."""
    import jax
    import numpy as np

    from repro.core.gemm import gemm_context

    from bench import correct, harness

    # programs of an earlier run in this process (limits.py runs many) are
    # not needed again
    jax.clear_caches()
    gc.collect()
    reference = cell.reference  # a module that is not there fails here, not after the window
    counter = host_events()
    traffic = cell.traffic(seed)
    built = harness.build(cell, seed)
    slots = cell.engine["slots"]
    with gemm_context(selector=built.selector, backend=backend) as ctx:
        t0 = time.perf_counter()
        gemms = harness.warm(built, cell, traffic, ctx.log)
        warm_s = time.perf_counter() - t0
        select_s = built.select_s[0]
        loop = harness.Loop(built.engine, host=counter.host)
        tracer = Tracer(loop, float(cell.mix.get("trace_s", TRACE_MAX_S)), rehearsal) if traced else None
        if traffic.closed:
            harness.fill(loop, traffic, slots)
        else:
            schedule = traffic.schedule(seconds)
        # what set-up made stays: the collector need not walk it in the window
        gc.collect()
        gc.freeze()
        counter.reset()
        if traffic.closed:
            window = harness.run_closed(loop, traffic, slots, seconds, tracer)
        else:
            window = harness.run_open(loop, schedule, seconds, float(cell.mix.get("drain_s", 60)), tracer)
        drain_end = loop.clock()
        in_window = (counter.compiles, counter.loads, counter.gc_s)
        gc.unfreeze()
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    tracked = list(loop.tracked.values())
    if traffic.closed:
        tracked = [t for t in tracked if any(window.holds(x) for x in t.token_t)]
    rec = harness.Record(
        workload=cell.workload, config=cell.config, slots=slots, window=window, steps=loop.steps,
        tracked=tracked, gemms=gemms, select_s=select_s, peaks=pk, drain_end=drain_end,
    )
    if tracer is not None:
        rec.trace, rec.program = tracer.reduce()

    finished = [
        correct.Served(np.asarray(t.req.prompt), np.asarray(t.req.out_tokens, np.int32), t.offer.max_new)
        for t in loop.tracked.values()
        if t.req is not None and t.req.done and not t.req.truncated and t.token_t and t.token_t[-1] <= drain_end
    ]
    # free the program's state before the reference runs
    built.engine.kv.pool = None
    loop.engine = built.engine = None
    gc.collect()
    t0 = time.perf_counter()
    cmp = correct.compare(
        reference, built.params, cell.config, finished, int(cell.engine["max_seq"]), control=control
    )
    cmp["seconds"] = time.perf_counter() - t0
    return dict(
        rec=rec, window=window, cmp=cmp, finished=len(finished), in_window=in_window, warm_s=warm_s,
        weights_s=built.weights_s, setup_s=window.open - T_START, peak=stats.get("peak_bytes_in_use"),
        lateness=[t.submitted - t.due for t in loop.tracked.values()], device=dev,
    )


def per_layer(cell, root: Path, rec) -> Dict:
    """Each per-layer metric of the cell, read by its reader in
    ``bench/metrics/<name>.py``; a reader that finds nothing is left out."""
    from bench import harness

    metrics = {}
    for i, m in enumerate(cell.per_layer()):
        v = harness.load_module(root / "bench" / "metrics" / f"{m['name']}.py", f"bench_metric_{i}").read(rec)
        if v is None:
            say(f"{m['name']}: nothing to read")
        else:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return metrics


def end_to_end(name: str, out: Dict) -> Optional[float]:
    from bench import measure

    rec = out["rec"]
    return {
        "setup_s": lambda: out["setup_s"],
        "output_tok_per_s": lambda: measure.output_tok_per_s(rec),
        "tpot_p95_ms": lambda: measure.tpot_p95_ms(rec),
        "ttft_p50_ms": lambda: measure.ttft_p50_ms(rec),
    }[name]()


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [str(CHECKOUT / "src"), str(CHECKOUT)]
    from bench import correct, harness, measure, peaks

    cell = harness.Cell.load(CHECKOUT, args.workload, rehearsal=args.cpu_rehearsal)

    import jax

    from repro.launch.cache import enable_compile_cache

    cache = "off (rehearsal)"
    if not args.cpu_rehearsal:
        # every program goes to the cache, however fast it compiled, so that
        # a later run of the cell compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if not args.cpu_rehearsal and dev.platform != "tpu":
        print(f"bench: no TPU found (platform {dev.platform!r}); nothing measured", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, found {len(devices)}", file=sys.stderr)
        return 1
    pk = peaks.peaks("TPU v5 lite" if args.cpu_rehearsal else dev.device_kind)
    say(f"workload {args.workload} seed {args.seed} seconds {args.seconds} trace {args.trace}; compile cache {cache}")

    backend = args.backend if args.cpu_rehearsal else "pallas"
    out = run_cell(
        cell, args.seed, args.seconds, traced=bool(args.trace), backend=backend, pk=pk, rehearsal=args.cpu_rehearsal
    )
    rec, cmp, window = out["rec"], out["cmp"], out["window"]
    late = sorted(out["lateness"])
    say(
        f"window {window.seconds:.3f} s, {len(rec.window_steps())} steps, {len(rec.tracked)} requests, "
        f"{measure.output_tokens(rec)} tokens; setup {out['setup_s']:.3f} s (weights {out['weights_s']:.3f} s, "
        f"warm-up {out['warm_s']:.3f} s, selection {rec.select_s * 1e3:.1f} ms)"
    )
    say(f"compiles in the window {out['in_window'][0]} (expected 0), compile-cache loads {out['in_window'][1]}")
    if late:
        say(f"generator lateness: max {late[-1] * 1e3:.3f} ms, p95 {measure.p95(late) * 1e3:.3f} ms")
    if rec.tracked and cell.mix["arrivals"]["kind"] != "closed":
        say(
            f"ttft over {len(rec.tracked)} requests: p50 {measure.ttft_ms(rec, 50):.3f} ms, "
            f"p95 {measure.ttft_ms(rec, 95):.3f} ms, max {measure.ttft_ms(rec, 100):.3f} ms"
        )
    longest = sorted(rec.window_steps(), key=lambda s: s.t0 - s.t1)[:5]
    say(
        "longest steps in the window (ms; garbage collection and compile-cache loads inside): "
        + ", ".join(
            f"{(s.t1 - s.t0) * 1e3:.1f} (gc {s.gc_s * 1e3:.1f}, loads {s.load_s * 1e3:.1f})" for s in longest
        )
    )
    say(f"garbage collection from the window's open to the drain's end: {out['in_window'][2] * 1e3:.1f} ms")
    say(f"peak_bytes_in_use {out['peak']}")
    say(
        f"reference: {cmp['requests']} of {out['finished']} finished requests, {cmp['compared']} served "
        f"tokens, {cmp['seconds']:.1f} s; logit gap widest {cmp['widest_gap']}, mean {cmp['mean_gap']}, "
        f"missed share {cmp['missed_share']}"
    )

    if args.trace:
        for chunk in (False, True):
            ok, n = measure.matched_steps(rec, chunk)
            pairs = collections.Counter(
                (d.kernels, rec.launches(rec.step_gemms(s))) for s, d in rec.traced() if (s.chunk is not None) == chunk
            )
            say(
                f"traced {'chunk' if chunk else 'decode-only'} steps: {n}, kernels as logged in {ok}; "
                f"(in trace, logged): count {dict(pairs)}"
            )
        metrics = per_layer(cell, CHECKOUT, rec)
    else:
        metrics = {}
        for m in cell.end_to_end():
            v = end_to_end(m["name"], out)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # closed loop: the requests served in the window; open loop: those due
    # in it, failed without a first token by the end of the drain
    attempted = len(rec.tracked)
    failed = sum(1 for t in rec.tracked if not t.token_t or (t.req is not None and t.req.truncated))
    checks = correct.checks(cmp, cell.limits)
    ok = correct.correct(cmp, cell.limits)
    line = {"correct": bool(ok), "attempted": attempted, "failed": failed, "metrics": metrics}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices), "memory_peak_bytes": out["peak"]}
    if args.trace:
        device.update(busy_s=rec.trace.busy_s, window_s=rec.trace.window_s)
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in rec.trace.device_ops],
            "idle_gaps": [[n, s] for n, s in rec.trace.idle_gaps],
        }
    line["device"] = device
    line["checks"] = checks
    if args.cpu_rehearsal:
        line = {"rehearsal": True, "correct": line["correct"], "readings": line["metrics"], "checks": checks}
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown, whose logging would follow the result lines
    os._exit(code)
