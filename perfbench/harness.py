"""One run of one cell: set up, drive the routed path for a fixed window of
open-loop traffic, drain, check against the plain reference, report.

The window drives the served path as a client sees it, all in this one
process (which holds the chip or chips):

    client -> Router.submit / Router.tick -> ActorReplicaHandle
           -> head actor_call -> worker thread -> ReplicaActor
           -> ServeEngine -> chip

Everything a cell needs is found by name: `BENCHMARK.json` names the cell's
configuration (`configs/<config>.json`) and traffic mix
(`traffic/<mix>.json`); the configuration's `family` names the module that
gives its program config, weights, reference and cost counts
(`families/<family>.py`); the limits of its comparison are in
`limits/<cell>.json`; every metric is read by `metrics/<metric>.py`. A
cell on n chips serves n one-chip replicas behind the router.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from perfbench import check, traffic, weights  # noqa: E402
from perfbench import trace as trace_mod  # noqa: E402
from perfbench.probes import Probes  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    cfg: Dict[str, Any]
    mix: Dict[str, Any]
    chips: int
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    family: ModuleType


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())
    return Cell(name, cfg, mix, int(w["chips"]), limits["limits"],
                [m for m in spec["end_to_end"] if _reports(m, name)],
                [m for m in spec["per_layer"] if _reports(m, name)],
                family(cfg["family"]))


def _module(path: Path, name: str) -> ModuleType:
    """The module in the file `path`, loaded once per process."""
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    mod = sys.modules.get(name)
    if mod is None or Path(mod.__file__) != path:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> Callable[[Any], Optional[float]]:
    path = BENCH / "metrics" / f"{metric}.py"
    return _module(path, f"perfbench_metric_{metric.replace('.', '_')}").read


def family(name: str, root: Path = BENCH) -> ModuleType:
    """The benchmark family `<root>/families/<name>.py`, which gives a
    configuration of that family its program config (`model_config(cfg)`),
    seeded weights in the served layout (`make_params(key, cfg)`), plain
    float32 reference and fp8 control (`logits_at(params, tokens, at, *,
    cfg, quant=None)`) and the counts the `model.*` readers divide
    (`decode_step_bytes(cfg, contexts)`, `decode_flops(cfg, contexts)`,
    `prefill_flops(cfg, n)`)."""
    return _module(root / "families" / f"{name}.py",
                   f"perfbench_family_{name}")


@dataclass
class Window:
    """Host-clock record of one measured window, seconds after it opened."""
    seconds: float
    cap_s: float
    sched: List[float]
    done: List[Optional[float]]          # None: shed or not finished by cap
    n_out: List[int]
    budget: List[int]
    setup_s: float = 0.0
    late: List[float] = field(default_factory=list)

    def latency_s(self) -> List[float]:
        """From scheduled send to the tick that handed back the last
        token; a failed request counts at the cap."""
        cap = self.seconds + self.cap_s
        return [(d if d is not None else cap) - s
                for s, d in zip(self.sched, self.done)]

    @property
    def failed(self) -> int:
        return sum(d is None for d in self.done)


@dataclass
class Readings:
    """What the metric readers read."""
    cell: Cell
    window: Window
    chips: int
    probes: Optional[Probes] = None
    lo: float = 0.0                      # window on time.perf_counter
    hi: float = 0.0
    trace: Optional[trace_mod.Trace] = None
    kind: str = ""                       # device_kind, the key into peaks

    def peaks(self) -> Dict[str, Any]:
        """This device's peaks; a kind not in `peaks.json` is an error."""
        table = json.loads((BENCH / "peaks.json").read_text())
        if self.kind not in table:
            raise KeyError(f"device kind {self.kind!r} is not in peaks.json")
        return table[self.kind]


def enable_cache() -> None:
    """JAX's persistent compile cache, for a run from the command line: in
    `$JAX_COMPILATION_CACHE_DIR`, which the entry points set to
    `<checkout>/.jax_cache`, keeping every program however fast it
    compiled, so a second run of a cell finds all of them."""
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def devices_for(chips: int, require_tpu: bool) -> List[Any]:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips asked, {len(devs)} found")
    return devs[:chips]


def warm(engine, lens) -> None:
    """Compile and run every program serving will use (prefill at each of
    the mix's prompt lengths, the cache scatter and the decode step, which
    takes its argmax and the slots' next positions inside it) by serving
    one request per length locally, each long enough to take the path of a
    request that goes on decoding."""
    from repro.serve.engine import Request
    for i, n in enumerate(lens):
        engine.add_request(Request(id=-1 - i, prompt=[1] * n,
                                   max_new_tokens=3))
    engine.run_until_drained()
    engine.stats = {k: 0 for k in engine.stats}


@contextlib.contextmanager
def counting_compiles():
    """Yields a one-item list that counts backend compiles (programs found
    in no cache) while the block runs."""
    from jax import monitoring
    n = [0]

    def on(event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1

    monitoring.register_event_duration_secs_listener(on)
    try:
        yield n
    finally:
        monitoring.unregister_event_duration_listener(on)


def drive(router, reqs, program_reqs, seconds: float, cap_s: float,
          probes: Optional[Probes]) -> Tuple[Window, float]:
    """Open loop: send each request at its scheduled time, tick the router
    in between, stop sending when the window closes, then drain up to the
    cap. Returns the record and the window's start on perf_counter."""
    span = probes.span if probes else (lambda _n: contextlib.nullcontext())
    n = len(reqs)
    done: List[Optional[float]] = [None] * n
    late: List[float] = []
    shed = set()
    t0 = time.perf_counter()
    end = t0 + seconds

    def harvest(fin):
        t = time.perf_counter() - t0
        for r in fin:
            done[r.id] = t

    def send_due(now):
        nonlocal i
        while i < n and t0 + reqs[i].at_s <= now:
            late.append(time.perf_counter() - (t0 + reqs[i].at_s))
            with span("router.submit"):
                if not router.submit(program_reqs[i]):
                    shed.add(i)
            i += 1
            now = time.perf_counter()

    i = 0
    with span(trace_mod.WINDOW_SPAN):
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            send_due(now)
            if not router.idle():
                with span("router.tick"):
                    harvest(router.tick())
            elif i < n:
                with span("client.idle"):
                    time.sleep(max(0.0, min(t0 + reqs[i].at_s, end)
                                   - time.perf_counter()))
            else:
                with span("client.idle"):
                    time.sleep(max(0.0, end - time.perf_counter()))
        send_due(end)             # due in the window, sent late: still sent
    while not router.idle() and time.perf_counter() < end + cap_s:
        harvest(router.tick())
    cap_end = seconds + cap_s
    for k in shed:
        done[k] = None
    done = [d if d is not None and d <= cap_end else None for d in done]
    n_out = [len(program_reqs[k].output) if done[k] is not None else 0
             for k in range(n)]
    return Window(seconds, cap_s, [r.at_s for r in reqs], done, n_out,
                  [r.max_new for r in reqs], late=late), t0


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, require_tpu: bool = True,
             control: Optional[str] = None,
             log=print
             ) -> Tuple[Dict[str, Any], Dict[str, Tuple[float, float]], Window]:
    """One run. Returns the result object, the numbers compared (each with
    its limit) and the window's record. With `control`, the control's
    numbers ride along in the result under `control` (the benchmark's own
    runs never ask for it)."""
    devices = devices_for(cell.chips, require_tpu)
    from repro.models import build_model
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.fleet import serve_fleet

    log(f"cell {cell.name} seed {seed} seconds {seconds} trace {int(traced)} "
        f"devices {[str(d) for d in devices]} cache "
        f"{jax.config.jax_compilation_cache_dir}")
    mix, cfg = cell.mix, cell.cfg
    slots, max_len = mix["engine"]["slots"], mix["engine"]["max_len"]
    reqs = traffic.generate(mix, seconds, seed, cfg["vocab_size"])
    model = build_model(cell.family.model_config(cfg))
    t = time.perf_counter()
    params = [weights.init_on_device(cell.family, cfg, seed, d)
              for d in devices]
    jax.block_until_ready(params)
    log(f"weights {sum(a.nbytes for a in jax.tree.leaves(params[0])) / 1e9:.3f}"
        f" GB per chip in {time.perf_counter() - t:.2f} s")
    t = time.perf_counter()
    engines = [ServeEngine(model, p, slots, max_len, device=d)
               for p, d in zip(params, devices)]
    for engine in engines:
        warm(engine, sorted(set(mix["prompt_len"]["support"])))
    log(f"engines built and warmed in {time.perf_counter() - t:.2f} s")
    probes = Probes() if traced else None
    if probes:
        for engine in engines:
            probes.wrap_engine(engine)
    program_reqs = [Request(id=r.id, prompt=r.prompt, max_new_tokens=r.max_new)
                    for r in reqs]
    tr = None
    with contextlib.ExitStack() as stack:
        fleet = stack.enter_context(serve_fleet(engines))
        if probes:
            for h in fleet.router.replicas.values():
                probes.wrap_handle(h)
            tdir = stack.enter_context(
                tempfile.TemporaryDirectory(prefix="perfbench-trace-"))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
        setup_s = time.perf_counter() - t_start
        if probes:
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
        with counting_compiles() as compiles:
            win, t0 = drive(fleet.router, reqs, program_reqs, seconds,
                            mix["drain_cap_s"], probes)
        in_window = compiles[0]
        if probes:
            jax.profiler.stop_trace()
        win.setup_s = setup_s
        peak = peak_bytes(devices)
        if probes:
            found = sorted(Path(tdir).rglob("*.xplane.pb"))
            t = time.perf_counter()
            tr = trace_mod.read(found[-1]) if found else None
            log(f"trace read in {time.perf_counter() - t:.2f} s")
    lat = np.asarray(win.latency_s())
    log(f"requests {len(reqs)} failed {win.failed}; generator late p50 "
        f"{np.percentile(win.late, 50) * 1e3:.2f} ms p90 "
        f"{np.percentile(win.late, 90) * 1e3:.2f} ms max "
        f"{max(win.late) * 1e3:.2f} ms; latency p50 "
        f"{np.percentile(lat, 50):.3f} s; compiles in window {in_window}; "
        f"router {dict(fleet.router.stats)}")

    rd = Readings(cell, win, cell.chips, probes, t0, t0 + seconds, tr,
                  devices[0].device_kind)
    wanted = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = reader(m["name"])(rd)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if traced:
        device["busy_s"] = tr.busy_s if tr else 0.0
        device["window_s"] = tr.window_s if tr else 0.0

    # the reference runs once the program's state is gone
    del engines, engine, fleet, rd, probes
    gc.collect()
    finished = [r for k, r in enumerate(program_reqs)
                if win.done[k] is not None]
    short = sum(len(r.output) != r.max_new_tokens for r in finished)
    sample = check.choose_sample(finished, seed, mix["check"])
    t = time.perf_counter()
    gaps = check.served_gaps(cell.family, params[0], cfg, sample, max_len,
                             mix["output_len"]["max"], control)
    log(f"reference over {len(sample)} requests, "
        f"{len(gaps['program'])} served tokens, in "
        f"{time.perf_counter() - t:.2f} s")
    nums = check.numbers(gaps["program"], short)
    checks = {k: (nums[k], cell.limits[k]) for k in cell.limits}
    result: Dict[str, Any] = {
        "correct": check.verdict(nums, cell.limits),
        "attempted": len(reqs), "failed": win.failed,
        "metrics": metrics, "device": device}
    if traced and tr:
        result["breakdown"] = trace_mod.breakdown(tr)
    if control:
        result["control"] = check.numbers(gaps["control"], 0)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result, checks, win

