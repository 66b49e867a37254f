"""One run of one cell: set-up, the measured window, the check.

What depends on the architecture comes from the configuration file's
module, ``archs/<model_type>.py`` (``archs.for_model``).  The harness
itself reads three keys of every configuration file: ``model_type``,
``vocab_size`` (the traffic's token range) and ``check.max_logit_gap``
(the limit of ``correct``).

Set-up builds the engine with the program's launcher
(``repro.launch.serve.make_engine``, weights made on the device from the
seed), then puts into its weights the leaves that the program's
initializer leaves at 0 and 1, drawn from the seed by the architecture's
reference (``seed_leaves``): dropping them would go unseen.  Set-up then
compiles the cell's one prefill and one decode shape (``engine.compile``)
and serves one warm-up batch of the cell's shape.

The window drives the public ``engine.submit`` / ``engine.run`` and does
not batch for the engine: a submitter thread submits each request at its
due time (open loop) while the main thread calls ``run`` whenever requests
wait.  Right after ``submit`` returns, the request's ``out_tokens`` becomes
a :class:`StampList`, which stamps ``perf_counter()`` on every token the
engine appends: each token's arrival on the host.  After ``seconds``
nothing more is submitted and every request submitted is served to its
end.

The check teacher-forces a sample of the served requests through the
architecture's plain reference (``served_gaps``) once the program's state
is freed.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Optional

import numpy as np

import archs
import traffic
import work
import xplane

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NO_TICK_LIMIT = 1 << 40      # engine.run's tick budget: never truncates
START_LEAD_S = 0.05          # from the window's set-up to its first due time
TRACE_S = 10.0               # the traced run profiles the window's last
                             # 10 s, about one batch's life
SPAN_PREFIX = xplane.SPAN_PREFIX


def read_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    model: dict
    traffic: dict
    end_to_end: list         # BENCHMARK.json metric entries this cell reports
    per_layer: list

    @classmethod
    def load(cls, spec: dict, name: str, root: Path = ROOT) -> "Cell":
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
        w = cells[name]
        cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]

        def mine(metrics):
            return [m for m in metrics
                    if name in m.get("workloads", [name])]
        return cls(name=name, chips=w["chips"],
                   model=read_json(root / cfg["file"]),
                   traffic=traffic.load(root / "chipbench" / "traffic"
                                        / f"{w['traffic']}.json"),
                   end_to_end=mine(spec["end_to_end"]),
                   per_layer=mine(spec["per_layer"]))


class StampList(list):
    """Stands in for a request's ``out_tokens``: stamps the host clock on
    every token added."""

    def __init__(self):
        super().__init__()
        self.stamps: list[float] = []

    def append(self, tok) -> None:
        super().append(tok)
        self.stamps.append(time.perf_counter())

    def extend(self, toks) -> None:
        for t in toks:
            self.append(t)

    def __iadd__(self, toks):
        self.extend(toks)
        return self


@dataclasses.dataclass
class Served:
    req: object              # the engine's Request
    due: float               # host clock: due (open loop) or submitted
    submitted: float
    n_asked: int
    prompt: np.ndarray

    @property
    def stamps(self) -> list:
        return getattr(self.req.out_tokens, "stamps", [])

    def fault(self) -> Optional[str]:
        out = self.req.out_tokens
        if not isinstance(out, StampList) or len(out.stamps) != len(out):
            return "a token without a stamp"
        if not self.req.done or len(out) != self.n_asked:
            return f"served {len(out)} of {self.n_asked} tokens"
        return None


@dataclasses.dataclass
class Run:
    """What a metric reader reads (``chipbench/metrics/<name>.py``)."""
    cell: Cell
    shape: object            # the architecture's work counts
    setup_s: float
    t0: float                # host clock of the window's start
    window_s: float
    served: list             # every request of the window, served
    stats: dict              # engine.stats over the window and the drain
    device_kind: str
    trace: Optional[xplane.Reduced] = None

    @property
    def peaks(self) -> dict:
        return work.peaks(self.device_kind)

    def window_tokens(self) -> list:
        """(request, token index) of every token stamped in the window."""
        end = self.t0 + self.window_s
        return [(s, i) for s in self.served
                for i, t in enumerate(s.stamps) if self.t0 <= t <= end]


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def build_engine(cell: Cell, seed: int):
    """The program's launcher's engine, with the seeded leaves of the
    architecture's reference in its weights."""
    import jax
    from repro.launch.serve import make_engine
    arch = archs.for_model(cell.model)
    engine = make_engine(arch.program_config(cell.model),
                         batch_size=cell.traffic["batch_size"],
                         max_seq=cell.traffic["max_seq"], seed=seed)
    arch.seed_leaves(engine.params, cell.model, seed)
    jax.block_until_ready(engine.params)
    return engine


def warm_up(engine, cell: Cell, seed: int) -> None:
    """Compile the cell's prefill and decode shapes, then serve one batch
    of the cell's shape outside the window."""
    engine.compile(cell.traffic["prompt_tokens"])
    reqs = [engine.submit(p, max_new_tokens=2) for p in
            traffic.warmup_prompts(cell.traffic, cell.model["vocab_size"],
                                   seed)]
    engine.run(max_ticks=NO_TICK_LIMIT)
    if not all(r.done and len(r.out_tokens) == 2 for r in reqs):
        raise RuntimeError("the warm-up batch was not served")


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def _pending(engine) -> bool:
    return any(not r.done for r in engine.queue)


def _sleep_until(t: float) -> None:
    delay = t - time.perf_counter()
    if delay > 0:
        time.sleep(delay)


def drive_open(engine, draws: list, t0: float) -> list:
    """Submit each draw at ``t0 + due_s`` from a thread; serve from this
    one.  Returns when every draw is submitted and served."""
    served, errors = [], []
    arrived, finished, stop = (threading.Event(), threading.Event(),
                               threading.Event())

    def submitter():
        try:
            for d in draws:
                due = t0 + d.due_s
                if stop.wait(max(0.0, due - time.perf_counter())):
                    return
                with span("submit"):
                    req = engine.submit(d.prompt, max_new_tokens=d.n_out)
                    req.out_tokens = StampList()
                served.append(Served(req, due, time.perf_counter(), d.n_out,
                                     d.prompt))
                arrived.set()
        except Exception as e:      # re-raised by the serving thread
            errors.append(e)
        finally:
            finished.set()
            arrived.set()

    th = threading.Thread(target=submitter, name="chipbench-submitter")
    th.start()
    try:
        while True:
            arrived.clear()
            if _pending(engine):
                with span("engine_run"):
                    engine.run(max_ticks=NO_TICK_LIMIT)
            elif finished.is_set():
                break
            else:
                with span("wait"):
                    arrived.wait(timeout=1.0)
    finally:
        stop.set()
        th.join()
    if errors:
        raise errors[0]
    return served


class Profiler:
    """Profiles the window's last ``TRACE_S`` seconds from a thread."""

    def __init__(self, trace_dir: Path, t0: float, seconds: float):
        self.dir = Path(trace_dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        self.error: Optional[Exception] = None
        self.thread = threading.Thread(
            target=self._run, args=(t0 + max(0.0, seconds - TRACE_S),
                                    t0 + seconds),
            name="chipbench-profiler")
        self.thread.start()

    def _run(self, start: float, stop: float) -> None:
        import jax
        try:
            _sleep_until(start)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans, not every call
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            _sleep_until(stop)
            jax.profiler.stop_trace()
        except Exception as e:      # re-raised by the serving thread
            self.error = e

    def file(self) -> Path:
        self.thread.join()
        if self.error is not None:
            raise self.error
        found = sorted(self.dir.glob("**/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no trace written under {self.dir}")
        return found[-1]


def drive(engine, cell: Cell, seed: int, seconds: float, t0: float) -> list:
    draws = traffic.draws(cell.traffic, cell.model["vocab_size"], seed,
                          seconds)
    return drive_open(engine, draws, t0)


# ---------------------------------------------------------------------------
# metrics and the check
# ---------------------------------------------------------------------------

def read_metric(name: str, run: Run) -> Optional[float]:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(run)
    return None if value is None else float(value)


def check_sample(served: list, k: int, seed: int) -> list:
    """The longest request and ``k - 1`` others drawn from the seed."""
    longest = max(range(len(served)), key=lambda i: served[i].n_asked)
    rest = [i for i in range(len(served)) if i != longest]
    k = min(k, len(served))
    pick = traffic.rng_for(seed, 5).choice(len(rest), k - 1, replace=False)
    return [served[longest]] + [served[rest[i]] for i in sorted(pick)]


def check(cell: Cell, seed: int, served: list, control: bool = False
          ) -> dict:
    sample = check_sample(served, cell.traffic["check_requests"], seed)
    gaps = archs.for_model(cell.model).served_gaps(
        cell.model, seed, [s.prompt for s in sample],
        [list(s.req.out_tokens) for s in sample],
        cell.traffic["output"]["max"], control=control)
    out = {"tokens_checked": int(gaps["gap"].size),
           "max_logit_gap": float(gaps["gap"].max())}
    if control:
        out["control_max_logit_gap"] = float(gaps["control_gap"].max())
    return out


def device_info(devices) -> dict:
    import jax
    d0 = devices[0]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": max(peaks)}


def _window_change(after, before):
    """A counter's change over the window; a value that is not a number,
    such as a size the backend does not report (None), as it stands."""
    if isinstance(after, (int, float)) and isinstance(before, (int, float)):
        return after - before
    return after


def serve_window(cell: Cell, seed: int, seconds: float, devices,
                 t_start: float, trace_dir: Optional[Path] = None):
    """Set-up and the window.  Returns (Run, device info, trace file or
    None) with the program's state freed."""
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    engine = build_engine(cell, seed)
    warm_up(engine, cell, seed)
    setup_s = time.perf_counter() - t_start
    before = dict(engine.stats)
    t0 = time.perf_counter() + START_LEAD_S
    prof = Profiler(trace_dir, t0, seconds) if trace_dir else None
    served = drive(engine, cell, seed, seconds, t0)
    trace_file = prof.file() if prof else None
    stats = {k: _window_change(engine.stats[k], v)
             for k, v in before.items()}
    info = device_info(devices)
    del engine
    gc.collect()
    run = Run(cell=cell, shape=archs.for_model(cell.model).shape(cell.model),
              setup_s=setup_s, t0=t0, window_s=seconds, served=served,
              stats=stats, device_kind=info["kind"])
    return run, info, trace_file


def run_cell(cell: Cell, seed: int, seconds: float, devices, t_start: float,
             trace_dir: Optional[Path] = None) -> dict:
    """One run; returns the result line's object."""
    run, info, trace_file = serve_window(cell, seed, seconds, devices,
                                         t_start, trace_dir)
    result = {"correct": False, "attempted": len(run.served), "failed": 0,
              "metrics": {}, "device": info}
    if trace_file is not None:
        run.trace = xplane.reduce(trace_file)
        info["busy_s"] = run.trace.busy_s
        info["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.device_ops,
                               "idle_gaps": run.trace.idle_gaps}
    for m in (cell.per_layer if trace_file is not None else cell.end_to_end):
        value = read_metric(m["name"], run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
    faults = [(s.req.rid, f) for s in run.served if (f := s.fault())]
    result["failed"] = len(faults)
    for rid, f in faults[:8]:
        print(f"chipbench: request {rid}: {f}", file=sys.stderr)
    good = [s for s in run.served if not s.fault()]
    limit = cell.model["check"]["max_logit_gap"]
    checks = {"failed_requests": {"value": len(faults), "limit": 0}}
    result["info"] = info_line(run)
    if good:
        c = check(cell, seed, good)
        checks["max_logit_gap"] = {"value": c["max_logit_gap"],
                                   "limit": limit}
        result["info"]["tokens_checked"] = c["tokens_checked"]
    result["correct"] = bool(good) and judge(checks)
    result["checks"] = checks
    return result


def judge(checks: dict) -> bool:
    """``correct``: every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def info_line(run: Run) -> dict:
    """Numbers printed on an earlier line and not judged."""
    late = [s.submitted - s.due for s in run.served]
    return {"requests": len(run.served),
            "output_tokens_per_s": len(run.window_tokens()) / run.window_s,
            "generator_late_p99_ms": float(np.percentile(late, 99) * 1e3)
            if late else None,
            "decode_steps": run.stats.get("decode_steps"),
            "prefills": run.stats.get("prefills"),
            "compiles": run.stats.get("compiles")}
