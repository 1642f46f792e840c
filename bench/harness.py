"""The benchmark's engine loop: load a cell, build the engine through the
program's own construction steps, warm it, drive it by its own clock.

Everything that belongs to one configuration, traffic mix or per-layer metric
is a file the harness finds by name under the benchmark root (the directory
that holds ``BENCHMARK.json``):

- ``BENCHMARK.json``'s ``configs[].file``: the configuration, which names
  its reference under ``reference`` and may scale drawn weights under
  ``weight_scale`` (below);
- ``bench/reference/<reference>.py``: the plain reference that decides
  ``correct``, imports nothing of the program and is given the same weight
  arrays as the program. It has ``RefConfig.from_config(config)``, a
  hashable (static) configuration made from the configuration file's dict,
  and ``gaps(params, tokens, nxt, ref_cfg, control)``: over one sequence
  ``tokens`` (S,) of prompt and served tokens, padded, and the token served
  after each position ``nxt`` (S,), it returns two (S,) arrays: how far the
  reference's logit of ``nxt[i]`` lies below its best at position i, and,
  with ``control``, the same gap of the token that the control (the
  reference one precision lower) puts first there, else zeros;
- ``bench/traffic/<mix>.json``: the mix, whose ``generator`` names a module
  ``bench/generators/<generator>.py`` with ``make(mix, seed, vocab)``;
- ``bench/cells/<workload>.json``: the limits that decide ``correct``;
- ``bench/metrics/<metric>.py``: a reader ``read(record)`` per per-layer
  metric, given the :class:`Record` of the run.

A configuration's ``weight_scale`` maps dotted key paths of the program's
``param_specs()`` tree (``layers.moe.router``) to a factor on that leaf's
drawn weights (``bench/weights.py``); each key is explained under the file's
``assumed``. Without it every leaf is drawn as before.

The loop keeps the time itself, with the host clock read after each
``engine.step()`` returns; it reads only the engine's public state
(``submit``, ``step``, ``active``, ``outstanding``, each request's
``out_tokens`` and ``prefilled``, and ``metrics()`` as the window opens and
closes) and the dispatch context's selection log.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

#: the checkout: ``bench/..``
CHECKOUT = Path(__file__).resolve().parents[1]

#: configuration-file keys and the program's ModelConfig fields they set
FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "num_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "rope_theta": "rope_theta",
    "torch_dtype": "dtype",
}


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything its files say."""

    workload: str
    root: Path
    benchmark: Dict
    entry: Dict
    config: Dict
    mix: Dict
    limits: Dict

    @classmethod
    def load(cls, root: Path, workload: str, rehearsal: bool = False) -> "Cell":
        bench = json.loads((root / "BENCHMARK.json").read_text())
        entries = {w["name"]: w for w in bench["workloads"]}
        if workload not in entries:
            raise SystemExit(f"no workload {workload!r} in {root / 'BENCHMARK.json'}; known: {sorted(entries)}")
        entry = entries[workload]
        cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
        config = json.loads((root / cfg_entry["file"]).read_text())
        mix = json.loads((root / "bench" / "traffic" / f"{entry['traffic']}.json").read_text())
        limits = json.loads((root / "bench" / "cells" / f"{workload}.json").read_text())
        if "reference" not in config:
            raise ValueError(f"{cfg_entry['file']} names no reference (a module of bench/reference/)")
        unexplained = set(config.get("weight_scale", {})) - set(config.get("assumed", {}))
        if unexplained:
            raise ValueError(f"{cfg_entry['file']}: weight_scale {sorted(unexplained)} not explained under assumed")
        if rehearsal:
            # tiny widths and lengths for a CPU rehearsal of the same code
            config = {**config, **config["rehearsal"]["config"], "engine": config["rehearsal"]["engine"]}
            mix = {**mix, **mix.get("rehearsal", {})}
            limits = {**limits, **limits.get("rehearsal", {})}
        return cls(workload, root, bench, entry, config, mix, limits)

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    @property
    def engine(self) -> Dict:
        return self.config["engine"]

    def end_to_end(self) -> List[Dict]:
        return [m for m in self.benchmark["end_to_end"] if self.workload in m.get("workloads", [self.workload])]

    def per_layer(self) -> List[Dict]:
        return [m for m in self.benchmark["per_layer"] if self.workload in m.get("workloads", [self.workload])]

    @functools.cached_property
    def reference(self):
        """The configuration's reference module, ``bench/reference/<name>.py``."""
        name = self.config["reference"]
        return load_module(self.root / "bench" / "reference" / f"{name}.py", f"bench_reference_{name}")

    def traffic(self, seed: int):
        gen = load_module(self.root / "bench" / "generators" / f"{self.mix['generator']}.py", "bench_generator")
        return gen.make(self.mix, seed, self.config["vocab_size"])


# -- building ------------------------------------------------------------


def model_config(cell: Cell):
    """The program's ModelConfig for the configuration file: the program's
    own config of ``program.arch`` with every size the file states, and the
    file's ``program.options``."""
    from repro.configs import get_config

    base = get_config(cell.config["program"]["arch"])
    kw = {FIELDS[k]: cell.config[k] for k in FIELDS if k in cell.config}
    kw.update(cell.config["program"].get("options", {}))
    return dataclasses.replace(base, **kw)


@dataclasses.dataclass
class Built:
    model: Any
    params: Any
    engine: Any
    selector: Any
    select_s: List[float]
    weights_s: float


def build(cell: Cell, seed: int) -> Built:
    """Weights from the seed, the selector, and the paged engine, through the
    program's construction steps (``serve.parse_args``, ``load_machine``,
    ``build_worker``, ``make_engine``). Call inside the gemm context the
    engine will serve in."""
    import jax

    from repro.dist.sharding import ArraySpec
    from repro.launch import serve
    from repro.models import build_model

    from bench import weights

    eng = cell.engine
    argv = [
        "--arch", cell.config["program"]["arch"], "--preset", "full", "--paged",
        "--slots", str(eng["slots"]), "--max-seq", str(eng["max_seq"]),
        "--page-size", str(eng["page_size"]), "--prefill-chunk", str(eng["prefill_chunk"]),
        "--seed", str(seed),
    ]
    args = serve.parse_args(argv)
    model = build_model(model_config(cell))
    t0 = time.perf_counter()
    params = weights.make(
        model.param_specs(), seed, lambda x: isinstance(x, ArraySpec), cell.config.get("weight_scale")
    )
    jax.block_until_ready(params)
    weights_s = time.perf_counter() - t0
    selector, _ = serve.build_worker(
        args, 0, mach=serve.load_machine(args), grid_sizes=serve.parse_grid_sizes(args), arch_cls=serve.DEFAULT_ARCH
    )
    select_s = [0.0]
    select = selector.select_op

    def timed_select(op):
        t = time.perf_counter()
        try:
            return select(op)
        finally:
            select_s[0] += time.perf_counter() - t

    selector.select_op = timed_select
    engine = serve.make_engine(args, model, params)
    return Built(model, params, engine, selector, select_s, weights_s)


# -- warm-up ---------------------------------------------------------------


def pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def pages_for(tokens: int, page: int) -> int:
    return max(1, -(-tokens // page))


def shapes(cell: Cell, traffic) -> Tuple[List[int], List[Tuple[int, int]]]:
    """The decode page-table widths and the (chunk size, table width) pairs
    of the jitted chunk step that this mix can reach."""
    eng = cell.engine
    page, chunk = eng["page_size"], eng["prefill_chunk"]
    classes = traffic.prompt_classes()
    longest = min(classes[-1] + traffic.max_output(), eng["max_seq"])
    lo, hi = pow2(pages_for(classes[0], page)), pow2(pages_for(longest, page))
    widths = []
    w = lo
    while w <= hi:
        widths.append(w)
        w *= 2
    chunks = set()
    for n in classes:
        width = pow2(pages_for(n, page))
        for start in range(0, n, chunk):
            chunks.add((min(chunk, n - start), width))
    return widths, sorted(chunks)


@dataclasses.dataclass
class Gemm:
    tag: str
    m: int
    n: int
    k: int
    g: int
    a_bytes: float
    b_bytes: float
    out_bytes: float
    tile: str  # the selected tile, which names its kernels


_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1, "int4": 0.5}


def gemm_of(entry) -> Gemm:
    op = entry.op
    m, n, k = op.local
    a, _, b = str(op.in_dtype).partition("*")
    return Gemm(
        entry.tag, m, n, k, op.g, _BYTES[a], _BYTES[b or a], _BYTES[str(op.out_dtype)], entry.selection.cfg.name
    )


def warm(built: Built, cell: Cell, traffic, log: List) -> Dict[str, List[Gemm]]:
    """Compile (or load from the compile cache) every program the window will
    run: the decode step at each table width, the chunk step at each shape
    (a request's first chunk among them), and one short request of each
    prompt length, which runs admission and sampling as the window does.
    Returns the GEMMs the decode step and each chunk size trace to."""
    import jax
    import jax.numpy as jnp

    engine = built.engine
    kv = engine.kv
    slots = cell.engine["slots"]
    widths, chunks = shapes(cell, traffic)
    gemms: Dict[str, List[Gemm]] = {}
    for w in widths:
        n = len(log)
        pages = jnp.full((slots, w), kv.scratch, jnp.int32)
        logits, kv.pool = engine._decode(
            engine.params, kv.pool, pages, jnp.zeros((slots, 1), jnp.int32), jnp.zeros((slots,), jnp.int32)
        )
        jax.block_until_ready(logits)
        if len(log) > n:
            gemms["decode"] = [gemm_of(e) for e in log[n:]]
    for size, w in chunks:
        n = len(log)
        logits, kv.pool = engine._chunk_step(
            engine.params, kv.pool, jnp.full((1, w), kv.scratch, jnp.int32),
            jnp.ones((1, size), jnp.int32), jnp.zeros((1,), jnp.int32),
        )
        jax.block_until_ready(logits)
        if len(log) > n:
            gemms[f"chunk{size}"] = [gemm_of(e) for e in log[n:]]
    for n in traffic.prompt_classes():
        engine.submit(np.ones(n, np.int32), max_new_tokens=2)
    while engine.step():
        pass
    jax.block_until_ready(kv.pool)
    return gemms


# -- the loop --------------------------------------------------------------


@dataclasses.dataclass
class Tracked:
    """One offered request as the benchmark saw it."""

    uid: int
    offer: Any  # the generator's Request
    due: float  # host clock
    submitted: float
    admitted: Optional[float] = None
    req: Any = None  # the engine's request object, once admitted
    token_t: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Step:
    """One ``engine.step()``: when it ran and what public state says it did."""

    index: int
    t0: float
    t1: float
    decode_ctx: List[int]  # keys attended by each row that decoded a token
    chunk: Optional[Tuple[int, int]]  # (start, size) of the prefill chunk it ran
    gc_s: float = 0.0  # host seconds of garbage collection inside the step
    load_s: float = 0.0  # host seconds of compile-cache loads inside the step

    @property
    def decode_rows(self) -> int:
        return len(self.decode_ctx)


class Loop:
    """Drives the engine and records steps and request times."""

    def __init__(
        self, engine, clock: Callable[[], float] = time.perf_counter,
        host: Callable[[], Tuple[float, float]] = lambda: (0.0, 0.0),
    ):
        self.engine = engine
        self.clock = clock
        self.host = host  # running host seconds of (garbage collection, compile-cache loads)
        self.tracked: Dict[int, Tracked] = {}
        self.waiting: Dict[int, Tracked] = {}
        self.live: List[Tracked] = []
        self.steps: List[Step] = []
        self.annotate = False
        self.sync_each_step = False

    def span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def submit(self, offer, due: float) -> Tracked:
        now = self.clock()
        uid = self.engine.submit(offer.prompt, max_new_tokens=offer.max_new)
        t = Tracked(uid, offer, due, now)
        self.tracked[uid] = t
        self.waiting[uid] = t
        return t

    def step(self) -> bool:
        """One engine step, recorded. Returns what ``engine.step()`` does."""
        import jax

        before = [(t, len(t.req.out_tokens), t.req.prefilled) for t in self.live]
        index = len(self.steps)
        gc0, load0 = self.host()
        t0 = self.clock()
        if self.annotate:
            with jax.profiler.StepTraceAnnotation("engine_step", step_num=index):
                progressed = self.engine.step()
                if self.sync_each_step:
                    jax.block_until_ready(self.engine.kv.pool)
        else:
            progressed = self.engine.step()
        t1 = self.clock()
        gc1, load1 = self.host()
        with self.span("bench.record"):
            if self.waiting:
                for r in self.engine.active:
                    t = self.waiting.pop(r.uid, None)
                    if t is not None:
                        t.admitted, t.req = t1, r
                        before.append((t, 0, 0))
                        self.live.append(t)
            decode_ctx: List[int] = []
            chunk = None
            for t, n_out, pre in before:
                r = t.req
                plen = len(r.prompt)
                gained = len(r.out_tokens) - n_out
                t.token_t.extend([t1] * gained)
                # a prompt that completes samples its first token, and the
                # same step's decode batch already takes it: a row that
                # decoded attends every key up to its input token's position
                first = int(pre < plen == r.prefilled)
                if gained > first:
                    decode_ctx.append(plen + n_out + first)
                if r.prefilled > pre:
                    chunk = (pre, r.prefilled - pre)
            self.live = [t for t in self.live if not t.req.done]
            self.steps.append(Step(index, t0, t1, decode_ctx, chunk, gc1 - gc0, load1 - load0))
        return progressed


@dataclasses.dataclass
class Window:
    open: float
    close: float
    trace_steps: Tuple[int, int] = (0, 0)  # [first, last) step indices traced
    engine_metrics: Optional[Tuple[Dict[str, float], Dict[str, float]]] = None  # engine.metrics() at open, at close

    @property
    def seconds(self) -> float:
        return self.close - self.open

    def holds(self, t: float) -> bool:
        return self.open < t <= self.close


def fill(loop: Loop, traffic, slots: int, max_steps: int = 2000) -> None:
    """Closed loop: submit and step until every slot decodes."""
    for _ in range(max_steps):
        while len(loop.waiting) < slots:
            loop.submit(traffic.next_closed(), loop.clock())
        loop.step()
        act = loop.engine.active
        if len(act) == slots and all(r.prefilled == len(r.prompt) for r in act):
            return
    raise RuntimeError(f"the engine did not reach {slots} decoding slots in {max_steps} steps")


def run_closed(loop: Loop, traffic, slots: int, seconds: float, tracer=None) -> Window:
    """Keep ``slots`` requests waiting; step for ``seconds``."""
    at_open = loop.engine.metrics()
    t_open = loop.clock()
    first = len(loop.steps)
    if tracer is not None:
        tracer.start()
    while True:
        with loop.span("bench.submit"):
            while len(loop.waiting) < slots:
                loop.submit(traffic.next_closed(), loop.clock())
        loop.step()
        if tracer is not None:
            tracer.maybe_stop(loop, t_open)
        if loop.steps[-1].t1 >= t_open + seconds:
            break
    at_close = loop.engine.metrics()
    if tracer is not None:
        tracer.stop(loop)
    w = Window(t_open, loop.steps[-1].t1, engine_metrics=(at_open, at_close))
    w.trace_steps = tracer.steps if tracer is not None else (first, first)
    return w


def run_open(loop: Loop, schedule, seconds: float, drain_s: float, tracer=None) -> Window:
    """Offer each request at its due time; step while there is work, wait
    while there is none; then serve every request due in the window to its
    end, for at most ``drain_s`` more."""
    at_open = loop.engine.metrics()
    t_open = loop.clock()
    end = t_open + seconds
    i = 0
    if tracer is not None:
        tracer.start()
    while True:
        now = loop.clock()
        if now >= end:
            break
        with loop.span("bench.submit"):
            while i < len(schedule) and t_open + schedule[i].due_s <= now:
                loop.submit(schedule[i], t_open + schedule[i].due_s)
                i += 1
        if loop.engine.outstanding():
            loop.step()
        else:
            nxt = t_open + schedule[i].due_s if i < len(schedule) else end
            with loop.span("bench.wait"):
                time.sleep(max(0.0, min(nxt, end) - loop.clock()))
        if tracer is not None:
            tracer.maybe_stop(loop, t_open)
    close = loop.clock()
    at_close = loop.engine.metrics()
    if tracer is not None:
        tracer.stop(loop)
    deadline = close + drain_s
    while loop.clock() < deadline and loop.engine.outstanding():
        loop.step()
    w = Window(t_open, close, engine_metrics=(at_open, at_close))
    w.trace_steps = tracer.steps if tracer is not None else (0, 0)
    return w


# -- the record given to the per-layer readers ---------------------------


@dataclasses.dataclass
class Record:
    """What a per-layer reader is given."""

    workload: str
    config: Dict
    slots: int
    window: Window
    steps: List[Step]
    tracked: List[Tracked]
    gemms: Dict[str, List[Gemm]]  # "decode", "chunk<size>" -> traced GEMMs
    select_s: float  # host seconds inside the selector during set-up
    peaks: Dict[str, float]
    drain_end: float  # when the loop stopped stepping
    trace: Any = None  # bench.trace.Reduction of the traced steps, or None
    program: Any = None  # bench.program_spans.Reduction of the traced window, or None

    @property
    def engine_metrics(self) -> Optional[Tuple[Dict[str, float], Dict[str, float]]]:
        """The engine's public ``metrics()`` as the window opened and as it
        closed: its counters, the program's spans' among them."""
        return self.window.engine_metrics

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if self.window.holds(s.t1)]

    def traced(self) -> List[Tuple[Step, Any]]:
        """(step, its device time) for each step the trace holds."""
        if self.trace is None:
            return []
        return [(s, self.trace.steps[s.index]) for s in self.steps if s.index in self.trace.steps]

    def step_gemms(self, s: Step) -> List[Gemm]:
        """The Pallas GEMMs one step ran, each once per launch: the decode
        step's, and the chunk step's of its size, a first chunk's too."""
        out = []
        if s.decode_rows:
            out += self.gemms.get("decode", [])
        if s.chunk is not None:
            out += self.gemms.get(f"chunk{s.chunk[1]}", [])
        return out

    def launches(self, gemms: List[Gemm]) -> int:
        """The GEMMs' runs in one step: every GEMM but the head runs once per
        layer of the scanned stack."""
        layers = self.config["num_hidden_layers"]
        return sum(1 if g.tag == "lm_head" else layers for g in gemms)

    def kernels_match(self, s: Step, d) -> bool:
        """Whether the step's Pallas kernels in the trace are the logged
        GEMMs': at least one launch per GEMM run (a Stream-K GEMM adds its
        fix-up and DP passes), and every kernel named by a logged tile."""
        gemms = self.step_gemms(s)
        tiles = {g.tile for g in gemms}
        return (
            bool(gemms)
            and d.kernels >= self.launches(gemms)
            and all(any(t in name for t in tiles) for name in d.kernel_names)
        )
