"""Serving benchmark: one cell, one seed, one measured window.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a config file
(``configs/``, which names its model lane in ``lanes/``), a traffic mix
(``traffic/``) and the cell's own serving sizes (``cells/<cell>.json``);
each metric is a reader in ``metrics/``.  All are found by name, so a
cell that reuses a mix is data alone, and a new architecture is a lane
file and a config file.

A run makes the weights on the device from the seed, builds the
program's engine and scheduler, warms every program the window calls by
serving one request through a whole life (a prefill round, a decode
round, retirement), then drives the scheduler on the wall clock for
``--seconds``.  Nothing may compile inside the window; the count is
printed.  After the window it reads the peak device memory, drops the
program's state, and runs the float32 reference over a sample of the
finished requests (``check.py``).  The last line of standard output is
the result; the numbers compared, each beside its limit, close standard
error.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics: the profiler starts at a round boundary in the
window's last seconds (``cells/<cell>.json`` ``trace.last_s``), the
rounds from the next boundary to the close (with the round in flight)
are the traced window, and the trace is written out after the close;
the result carries the device's busy time and a breakdown.

Without an accelerator, or with fewer than the cell's chips, the run
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    pass


def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, found by name from ``BENCHMARK.json``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench = root / "bench"

    def applies(m):
        return name in m.get("workloads", [name])

    return dict(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / cfg["file"]).read_text()),
        mix=json.loads((bench / "traffic" / f"{w['traffic']}.json")
                       .read_text()),
        params=json.loads((bench / "cells" / f"{name}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)])


def read_metric(name: str, run: dict):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


@contextlib.contextmanager
def compile_events():
    """Compilations and persistent-cache loads inside the block."""
    import jax
    box = [0]

    def on_duration(event, secs, **_):
        if event in _COMPILE_EVENTS:
            box[0] += 1

    def on_event(event, **_):
        if event == _CACHE_HIT:
            box[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield box
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform == "cpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} accelerator chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def warm_up(server, mix_vocab: int, chunk: int):
    """Serve one request through a whole life: admission, one prefill
    round that completes the prompt and samples its first token, one
    decode round, retirement.  That compiles (or loads) every program
    the window calls, at the window's shapes."""
    server.submit([1 + (i % (mix_vocab - 1)) for i in range(chunk)], 2)
    while server.has_work:
        server.step()


class Window:
    """Drives the scheduler on the wall clock and records what the
    metric readers need (see ``readers.py``)."""

    def __init__(self, server, requests, seconds, trace_plan=None):
        self.server = server
        self.requests = requests
        self.seconds = float(seconds)
        self.trace_plan = trace_plan      # (start_s, log_dir)
        self.rec = [dict(due=r.due_s, admit=None, first=None, deliveries=[],
                         prompt_len=len(r.prompt), n=0, pos=0, submitted=None)
                    for r in requests]
        self.rounds = []
        self.finished = {}                # request index -> tokens
        self.failed = 0

    def _observe(self, ts, te, done, steps, of_rid):
        prog = self.server.progress()
        for rid, toks in done:
            i = of_rid[rid]
            self.finished[i] = toks
            prog[rid] = (len(toks), self.rec[i]["prompt_len"] + len(toks) - 1)
        emitted = firsts = 0
        spans = []
        for rid, (n, pos) in prog.items():
            r = self.rec[of_rid[rid]]
            if r["admit"] is None:
                r["admit"] = ts
            dn = n - r["n"]
            if dn > 0:
                if r["n"] == 0:
                    r["first"] = te
                    firsts += 1
                r["deliveries"].append([te, n])
                emitted += dn
            spans.append([r["pos"], pos, dn])
            r["n"], r["pos"] = n, pos
        return dict(start=ts, end=te, steps=steps, emitted=emitted,
                    first_tokens=firsts, spans=spans, traced=False)

    def run(self):
        import jax
        from jax.profiler import TraceAnnotation
        srv, reqs = self.server, self.requests
        of_rid, nxt = {}, 0
        steps = srv.stats["steps_run"]
        profiling, traced_span = False, None
        clock = time.perf_counter
        t0 = clock()
        while True:
            now = clock() - t0
            with TraceAnnotation("bench.submit"):
                while nxt < len(reqs) and reqs[nxt].due_s <= now:
                    r = reqs[nxt]
                    try:
                        of_rid[srv.submit(r.prompt, r.max_new_tokens)] = nxt
                        self.rec[nxt]["submitted"] = clock() - t0
                    except ValueError:
                        self.failed += 1
                    nxt += 1
            if now >= self.seconds:
                break
            if profiling and traced_span is None:
                # the round after the profiler starts is traced: the first
                # one under the profiler paid a one-off stall of 2.3 s
                traced_span = TraceAnnotation("bench.traced")
                traced_span.__enter__()
            if self.trace_plan and not profiling \
                    and now >= self.trace_plan[0] and srv.has_work:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.trace_plan[1],
                                         profiler_options=opts)
                profiling = True
            if srv.has_work:
                ts = clock() - t0
                with TraceAnnotation("bench.step"):
                    done = srv.step()
                te = clock() - t0
                with TraceAnnotation("bench.observe"):
                    s = srv.stats["steps_run"]
                    rnd = self._observe(ts, te, done, s - steps, of_rid)
                    steps = s
                    rnd["traced"] = traced_span is not None
                    self.rounds.append(rnd)
            else:
                due = reqs[nxt].due_s if nxt < len(reqs) else self.seconds
                with TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(due, self.seconds) - now))
        t_close = clock()
        if traced_span is not None:
            traced_span.__exit__(None, None, None)
        if profiling:
            # the trace is written after the close, outside the window
            jax.profiler.stop_trace()
        self.n_submitted = nxt
        return t0, t_close


def run_cell(cell: dict, seed: int, seconds: int, trace: bool, *,
             require_chip: bool = True, server_factory=None,
             control: bool = False) -> tuple:
    """One run of ``cell``; returns ``(result, log_lines, check_lines,
    record)``, the record being what the metric readers read.

    ``server_factory`` replaces ``program.Server`` (the tests break the
    timed path underneath with it); ``require_chip=False`` lets a test
    drive the rest of a run on the CPU.  ``control=True`` also reads the
    fp8 control on the same sample (``limits.py``; runs never do)."""
    import numpy as np

    import check
    import traffic
    import weights

    devs = devices(cell["chips"], require_chip)
    import program
    program.enable_compile_cache()
    c, p, mix = cell["config"], cell["params"], cell["mix"]

    w = weights.make(c, seed)
    factory = server_factory or program.Server
    server = factory(c, p, w, seed)
    reqs = traffic.generate(mix, p, seed, seconds, c["vocab_size"])
    warm_up(server, c["vocab_size"], p["chunk_size"])

    plan = None
    log_dir = str(TRACE_DIR)
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)
        plan = (max(0.0, seconds - float(p["trace"]["last_s"])), log_dir)
    win = Window(server, reqs, seconds, plan)
    with compile_events() as compiles:
        t0, t_done = win.run()

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    stats = server.stats
    server.close()
    del server
    gc.collect()

    t_check = time.perf_counter()
    finished = [(reqs[i].prompt, toks) for i, toks in win.finished.items()]
    readings = check.compare(c, w, finished, seed, int(p["check"]["sample"]))
    correct, checks = check.verdict(readings, p["check"])
    if control:
        readings["control"] = check.compare(
            c, w, finished, seed, int(p["check"]["sample"]), "fp8")
    t_checked = time.perf_counter()

    run = dict(cell=p, config=c, seconds=float(seconds),
               setup_s=t0 - T_PROCESS, requests=win.rec, rounds=win.rounds,
               device=dict(kind=devs[0].device_kind), trace=None)
    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs), memory_peak_bytes=peak)
    breakdown = None
    if trace:
        from jax.profiler import ProfileData

        import tracefile as trace_mod
        red = trace_mod.reduce(ProfileData.from_file(
            trace_mod.find_xplane(log_dir)))
        shutil.rmtree(log_dir, ignore_errors=True)
        run["trace"] = red
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = dict(device_ops=red["device_ops"],
                         idle_gaps=red["idle_gaps"])
    metrics = {}
    for m in (cell["per_layer"] if trace else cell["end_to_end"]):
        v = read_metric(m["name"], run)
        if v is not None:
            metrics[m["name"]] = dict(value=v, unit=m["unit"])

    late = [r["submitted"] - r["due"] for r in win.rec
            if r["submitted"] is not None]
    log = [
        f"cell {cell['name']} seed {seed} window {seconds} s on "
        f"{device['count']} x {device['kind']}",
        f"compiles_in_window {compiles[0]}",
        f"generator lateness: max {max(late, default=0.0):.4f} s, p50 "
        f"{float(np.median(late)) if late else 0.0:.4f} s over "
        f"{len(late)} submissions (requests wait for the round in flight)",
        f"rounds {len(win.rounds)}, requests due {len(reqs)}, submitted "
        f"{win.n_submitted}, finished {len(win.finished)}, scheduler "
        f"{json.dumps(stats)}",
        f"window overran by {t_done - t0 - seconds:.3f} s (the round in "
        f"flight at the close); check took {t_checked - t_check:.1f} s: "
        f"{json.dumps(readings)}",
    ]
    if compiles[0]:
        correct = False
        log.append("compilations inside the window: the run is not sound")
    checks["compiles_in_window"] = dict(value=compiles[0], limit=0,
                                        rule="<=")
    result = dict(correct=correct, attempted=len(reqs), failed=win.failed,
                  metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["readings"] = readings
    result["checks"] = checks
    check_lines = [f"check {k}: {v['value']} (limit {v['rule']} "
                   f"{v['limit']})" for k, v in checks.items()]
    return result, log, check_lines, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the persistent compile cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    cell = load_cell(args.workload)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        result, log, check_lines, _ = run_cell(
            cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for line in log:
        print(line, flush=True)
    for line in check_lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
