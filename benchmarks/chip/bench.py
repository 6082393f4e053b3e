"""One run of one benchmark cell on the chip.

    python benchmarks/chip/bench.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is found by name in BENCHMARK.json: its configuration file
(configs/), the family module of the file's model_type
(families/<model_type>.py: the model's sizes, weights, program config,
reference and work counts), its traffic file (traffic/), its limits
(limits/<cell>.json) and the reader of each metric
(metrics/<metric>.py).  A run builds the serving engine with weights
made from the seed, warms every program the cell's traffic can use,
serves the requests that arrive in [0, seconds) and drains them, then
checks a sample of what it served against the float32 reference.  The
last line of standard output is one JSON object; the numbers compared,
each beside its limit, are the last lines of standard error and the
result's last key.  With --trace 1 the metrics are the per-layer ones,
read from a profiler trace of a few seconds inside the window.

It exits nonzero with no result line when JAX finds no TPU, fewer chips
than the cell asks for, or a device kind that peaks.json lacks.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE = HERE / ".cache"
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import costs  # noqa: E402
import traffic as T  # noqa: E402

TRACE_S = 3.0        # length of the traced part of a --trace 1 window
SAMPLE_TOKENS = 320  # served tokens the correctness sample holds at least,
# unless the cell's limits file sets its own `sample_tokens`
NO_READING = 1e30    # the gap when nothing was served to compare
DRAIN_LIMIT_S = 150.0  # a request unfinished this long after the window fails


class DrainLimit(Exception):
    pass


def limit_drain(engine, deadline: list) -> None:
    """Stop engine.run() once the clock passes deadline[0]: what has not
    finished by then never comes."""
    sched = engine.sched
    for attr in ("reconcile", "admission_cycle"):
        fn = getattr(sched, attr)

        def guarded(*a, _fn=fn, **k):
            if time.perf_counter() > deadline[0]:
                raise DrainLimit()
            return _fn(*a, **k)

        setattr(sched, attr, guarded)


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(spec: dict, workload: str):
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return cell, conf


def require_chip(chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX found {devs[0].platform})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return costs.peaks(devs[0].device_kind)


def enable_cache() -> None:
    """JAX's persistent compilation cache at one fixed path in the
    checkout, for every program however fast it compiled."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class Compiles:
    """Backend compiles and persistent-cache loads, with their host times."""

    def __init__(self):
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append(("compile", time.perf_counter()))

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.events.append(("load", time.perf_counter()))

    def count(self, kind: str, t_from: float, t_to: float) -> int:
        return sum(k == kind and t_from <= t < t_to for k, t in self.events)


COMPILES = None


class TracedWindow:
    """Starts the profiler at a dispatch boundary `start_s` into the
    window and stops it at the first boundary TRACE_S later, logging in
    order the work (decode keys and FLOPs, prefill FLOPs) of what the host
    sent to the device in between."""

    def __init__(self, family, s, out_dir: Path, start_s: float):
        self.family, self.s = family, s
        self.dir, self.start_s = out_dir, start_s
        self.state, self.t_run = "idle", None
        self.log = []  # ("decode", keys, flops) | ("prefill", 0, flops)
        self.t_on = self.t_off = None

    def _start(self):
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.state, self.t_on = "on", time.perf_counter()

    def stop(self):
        if self.state == "on":
            jax.profiler.stop_trace()
            self.state, self.t_off = "off", time.perf_counter()

    def on_dispatch(self, lanes, n, now):
        if self.state == "idle" and self.t_run is not None \
                and now >= self.t_run + self.start_s:
            self._start()
        elif self.state == "on":
            keys = flops = 0
            f, s = self.family, self.s
            for pos, steps, emitted in lanes:
                k = costs.decode_keys(pos, steps)
                keys += k
                flops += (f.token_flops(s, 0, False) * steps
                          + f.attn_flops_per_key(s) * k
                          + f.head_flops(s) * emitted)
            self.log.append(("decode", keys, flops))
            if now >= self.t_on + TRACE_S:
                self.stop()

    def on_prefill(self, lengths):
        if self.state == "on":
            self.log.append(("prefill", 0, sum(
                self.family.prefill_flops(self.s, n) for n in lengths)))

    def work(self, n_decode: int):
        """Keys and FLOPs of the last `n_decode` decode dispatches and of
        the prefills sent after the first of them."""
        idx = [i for i, e in enumerate(self.log) if e[0] == "decode"]
        part = self.log[idx[-n_decode]:] if 0 < n_decode <= len(idx) else []
        return (sum(e[1] for e in part), sum(e[2] for e in part))


def buckets_for(longest: int):
    out, b = [], 16
    while True:
        out.append(b)
        if b >= longest:
            return out
        b *= 2


def _module(path: Path, name: str):
    """The module at `path`, loaded once under `name`."""
    path = Path(path).resolve()
    mod = sys.modules.get(name)
    if mod is not None and Path(mod.__file__) == path:
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up while made
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The `read(run)` of metrics/<name>.py."""
    return _module(HERE / "metrics" / f"{name}.py", f"metric_{name}").read


def load_family(model_type, where: Path = HERE / "families"):
    """The family module <where>/<model_type>.py; a model_type with none
    fails, naming the families there."""
    path = Path(where) / f"{model_type}.py"
    if not isinstance(model_type, str) or not path.is_file():
        known = sorted(p.stem for p in Path(where).glob("*.py"))
        raise SystemExit(f"bench: no model family {model_type!r} in "
                         f"{where}; known families: {known}")
    return _module(path, f"family_{model_type}")


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def sample(done, seed: int, tokens: int = SAMPLE_TOKENS):
    """Finished requests to check: the first to arrive, the longest, then
    others drawn from the seed until `tokens` served tokens."""
    ok = [r for r in done if r.done and r.tokens_out]
    if not ok:
        return []
    pick = [ok[0], max(ok, key=lambda r: len(r.prompt) + len(r.tokens_out))]
    rng = T.seed_rng(seed, 3)
    for i in rng.permutation(len(ok)):
        if sum(len(r.tokens_out) for r in set(pick)) >= tokens:
            break
        pick.append(ok[i])
    seen, out = set(), []
    for r in pick:
        if r.rid not in seen:
            seen.add(r.rid)
            out.append(r)
    return out


def keep_slice(rec: dict, red: dict, path: str, span_ns: float = 30e6):
    """Write ~30 ms of the trace from its middle, and every op's total."""
    dev = sorted(rec["device"], key=lambda e: e[1])
    if not dev:
        return
    a = dev[len(dev) // 2][1]
    b = a + span_ns
    part = {"device": [e for e in dev if a <= e[1] < b],
            "host": [e for e in rec["host"] if e[1] < b and e[1] + e[2] > a]}
    Path(path).write_text(json.dumps({"slice": part, "op_s": red["op_s"]}))


def gap_numbers(gaps) -> dict:
    """The numbers a run's logit gaps are compared by: the widest gap of
    any sampled served token, and the mean over them all."""
    if not gaps:
        return {"max_logit_gap": NO_READING, "mean_logit_gap": NO_READING}
    flat = np.concatenate(gaps)
    return {"max_logit_gap": float(flat.max()),
            "mean_logit_gap": float(flat.mean())}


def run_cell(spec: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, on_chip: bool = True, rate_scale: float = 1.0,
             limits: dict | None = None, tamper=None, log=print,
             traffic_dir: Path = HERE / "traffic",
             t_start: float = T_PROCESS, drain_limit_s: float = DRAIN_LIMIT_S,
             keep_trace: str | None = None, control: bool = False,
             families_dir: Path = HERE / "families") -> dict:
    """Set up, serve the window, drain, check.  `tamper(engine)` may break
    the timed path (the harness's own tests); `limits` overrides
    limits/<cell>.json (each number compared, by name, with its limit);
    `keep_trace` names a file for a slice of the trace and `control` also
    reads the family's controls (calibration); `families_dir` is where the
    configuration's family module is looked for."""
    import reference
    import system as S

    cell, conf = find_cell(spec, workload)
    config = json.loads((ROOT / conf["file"]).read_text())
    family = load_family(config.get("model_type"), families_dir)
    s = family.shapes(ROOT / conf["file"], conf["name"])
    serve = config["serve"]
    tr = T.load(traffic_dir / f"{cell['traffic']}.json")
    if rate_scale != 1.0:
        tr = dict(tr, sessions_per_s=tr["sessions_per_s"] * rate_scale)
    if limits is None:
        limits = json.loads((HERE / "limits" / f"{workload}.json").read_text())
    longest, longest_out = T.bounds(tr)
    buckets = buckets_for(longest)
    global COMPILES
    if COMPILES is None:
        COMPILES = Compiles()
    compiles = COMPILES
    if on_chip and S.kernel_route() != "pallas":
        raise SystemExit(f"bench: kernel route is {S.kernel_route()!r}, "
                         f"not 'pallas'")

    t = {"start": time.perf_counter() - t_start}
    t0 = time.perf_counter()
    params = family.served_params(s, seed)
    jax.block_until_ready(params)
    mcfg = family.model_config(s)
    S.check_layout(mcfg, params)
    t["weights"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    engine = S.build_engine(mcfg, params, serve["max_batch"], buckets,
                            longest_out)
    del params
    t["engine"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    sched = T.generate(tr, s.vocab, seed, seconds)
    pairs = S.warm_pairs(buckets, [(len(x.prompt), x.budget) for x in sched])
    S.warm(engine, pairs, s.vocab, seed + 7)
    t["warm"] = time.perf_counter() - t0
    kernel = None
    if on_chip:
        t0 = time.perf_counter()
        kernel = S.decode_has_kernel(engine)
        t["kernel_check"] = time.perf_counter() - t0
        if not kernel:
            raise SystemExit("bench: the paged decode program holds no "
                             "tpu_custom_call")

    reqs = [S.TimedRequest(rid=x.rid, prompt=x.prompt,
                           max_new_tokens=x.budget, t_arrival=x.t_arrival)
            for x in sched]
    tw = None
    if trace:
        tw = TracedWindow(family, s, CACHE / "trace", 0.4 * seconds)
        S.install_counts(engine, tw.on_dispatch, tw.on_prefill)
        S.install_spans(engine)
    if tamper is not None:
        tamper(engine)
    deadline = [float("inf")]
    limit_drain(engine, deadline)
    for r in reqs:
        engine.submit(r)
    base = dict(engine.stats)
    t_setup_end = time.perf_counter()
    deadline[0] = t_setup_end + seconds + drain_limit_s
    if tw is not None:
        tw.t_run = t_setup_end
    try:
        engine.run()
    except DrainLimit:
        log(f"drain: stopped {drain_limit_s}s after the window")
    finally:
        if tw is not None:
            tw.stop()
    done = [r for r in reqs if r.done]
    t_end = time.perf_counter()
    t_win0 = min((r.t_enqueue - r.t_arrival for r in reqs if r.t_enqueue),
                 default=t_setup_end)
    setup_s = t_win0 - t_start
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    stats = {k: v - base.get(k, 0) for k, v in engine.stats.items()}
    log(f"setup: {setup_s:.3f}s in all; imports and device "
        f"{t['start']:.3f}s, weights {t['weights']:.3f}s, engine "
        f"{t['engine']:.3f}s, traffic and warm {t['warm']:.3f}s over "
        f"{len(pairs)} (bucket, pages) admissions in buckets "
        f"{sorted({b for b, _ in pairs})} and the decode ladder"
        + (f", kernel check {t['kernel_check']:.3f}s" if on_chip else "")
        + f"; compiles in set-up {compiles.count('compile', t_start, t_win0)}, "
        f"cache loads {compiles.count('load', t_start, t_win0)}; in the window "
        f"compiles {compiles.count('compile', t_win0, t_win0 + seconds)}, "
        f"cache loads {compiles.count('load', t_win0, t_win0 + seconds)}")
    log(f"window: {len(reqs)} requests arrived in {seconds}s, drained "
        f"{t_end - t_win0 - seconds:.3f}s after it; engine stats {stats}")

    # correctness: the engine's state goes before the reference runs
    check = sample(done, seed, int(limits.get("sample_tokens",
                                              SAMPLE_TOKENS)))
    served = [(r.prompt, np.asarray(r.tokens_out, np.int32)) for r in check]
    del engine
    gc.collect()
    t0 = time.perf_counter()
    gaps, ctl = (reference.served_gaps(family, s, seed, served,
                                       family.CONTROLS if control else ())
                 if served else ([], {}))
    t_ref = time.perf_counter() - t0
    numbers = gap_numbers(gaps)
    n_tok = sum(len(g) for g in gaps)
    agree = (sum(int((g == 0).sum()) for g in gaps) / n_tok) if n_tok else 0.0
    ctl_numbers = {q: gap_numbers(ctl.get(q, [])) for q in ctl}
    log(f"reference: {len(check)} requests, {n_tok} served tokens, "
        f"{t_ref:.3f}s; served token is the reference's best at "
        f"{agree:.4f}; {numbers}"
        + (f"; controls at the same positions {ctl_numbers}"
           if control else ""))
    failed = sum(len(r.tokens_out) != r.max_new_tokens for r in reqs)
    checks = {k: [numbers[k], float(lim)] for k, lim in limits.items()
              if k in numbers}
    if not checks:
        raise SystemExit(f"bench: the limits name none of {sorted(numbers)}")
    checks["failed_requests"] = [failed, 0]
    correct = bool(check) and all(v <= lim for v, lim in checks.values())

    run = SimpleNamespace(
        requests=reqs, seconds=seconds, t0=t_win0, setup_s=setup_s,
        stats=stats, max_batch=serve["max_batch"], shapes=s, family=family,
        peak=costs.peaks(dev.device_kind) if on_chip else None,
        trace=None)
    breakdown, busy = None, None
    if trace and tw is not None and tw.t_off is not None:
        import devtrace as TR
        rec = TR.load(str(tw.dir), S.SPANS)
        # the device trace can begin some dispatches after start_trace
        # returns: the window opens at the first decode program it holds,
        # and the work counted is that of the dispatches it holds
        runs = TR.runs_of(rec, S.DECODE_PROGRAM)
        red = TR.reduce(rec, since=runs[0][0] if runs else None)
        red["keys"], red["flops"] = tw.work(len(runs))
        n_dec = sum(e[0] == "decode" for e in tw.log)
        host = sorted(e[1] for e in rec["host"] if e[0] == "decode_dispatch")
        log(f"trace: {n_dec} decode dispatches sent while tracing, "
            f"{len(runs)} decode programs in the device trace; host "
            f"bracket {tw.t_off - tw.t_on:.3f}s, device window "
            f"{red['window_s']:.3f}s, busy {red['busy_s']:.3f}s"
            + (f"; first decode program {(runs[0][0] - host[0]) / 1e6:.1f} "
               f"ms after the first traced dispatch span, last ends "
               f"{(runs[-1][1] - host[-1]) / 1e6:.1f} ms after the last"
               if runs and host else ""))
        if keep_trace:
            keep_slice(rec, red, keep_trace)
        run.trace = red
        shutil.rmtree(tw.dir, ignore_errors=True)
        busy = (red["busy_s"], red["window_s"])
        breakdown = {"device_ops": red["top_ops"], "idle_gaps": red["gaps"]}
    kind = "per_layer" if trace else "end_to_end"
    metrics, other = {}, {}
    for k in ("end_to_end", "per_layer"):
        for m in spec[k]:
            if not applies(m, workload):
                continue
            v = reader(m["name"])(run)
            if v is not None and np.isfinite(v):
                if k == kind:
                    metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
                else:
                    other[m["name"]] = float(v)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    if busy is not None:
        device.update(busy_s=busy[0], window_s=busy[1])
    out = {"correct": correct, "attempted": len(reqs), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    out["_extra"] = {"numbers": numbers, "control": ctl_numbers,
                     "agree": agree, "kernel": kernel,
                     "drain_s": t_end - t_win0 - seconds, "setup": t,
                     "stats": stats, "other_metrics": other}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec()
    cell, _ = find_cell(spec, args.workload)
    require_chip(int(cell["chips"]))
    enable_cache()
    out = run_cell(spec, args.workload, args.seed, args.seconds,
                   bool(args.trace), log=lambda m: print(m, flush=True))
    out.pop("_extra")
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
