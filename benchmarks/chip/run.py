"""One run of one benchmark cell on the chip.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
      --seconds <run_seconds> --trace <0|1>

From the root of a checkout.  The cell (``BENCHMARK.json``) names a
configuration (``configs/<name>.json``, whose ``arch`` names its
architecture module, ``archs/<arch>.py``), a traffic mix
(``traffic/<name>.json``) and its chips: that many one-chip replicas, one
engine on each device, behind ``ReplicaSet`` under the mix's ``router``
policy (one chip: the one engine, no router).  The run

  1. makes the weights from the seed on each chip in one jitted call and
     builds an ``InferenceEngine`` there (DPU control, mitigation on);
  2. warms up the decode step and the prefill buckets this traffic uses,
     with JAX's persistent compilation cache in ``<checkout>/.jax_cache``
     (or ``$JAX_COMPILATION_CACHE_DIR``); set-up ends when the window opens;
  3. drives the open loop on the host clock for ``--seconds``
     (``serve_loop``), traced by the profiler with ``--trace 1``: the
     whole window, or its last ``trace_seconds`` where the mix names them
     (a trace of every chip over the whole window takes too long to
     read);
  4. reads the peak memory of each device, frees the engines, and
     compares a seeded sample of the finished requests, the longest among
     them and one of every replica, with the float32 reference
     (``reference``): the widest gap by which a served token's reference
     logit lies below the reference's best;
  5. prints, as the last line of stdout, one JSON object: ``correct``,
     ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
     or with ``--trace 1`` its per-layer ones), ``device``, ``breakdown``
     in a traced run, and last ``checks``: each compared number beside
     its limit, also the last lines of stderr.

Without a TPU, or on a host with fewer devices than the cell's chips, it
exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import random  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
if sys.path and Path(sys.path[0] or ".").resolve() == ROOT / "benchmarks" \
        / "chip":
    sys.path.pop(0)          # run as a script: its directory shadows nothing
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402

from benchmarks.chip import reference, spec, stats, traffic, weights  # noqa: E402
from benchmarks.chip import xtrace  # noqa: E402
from benchmarks.chip.serve_loop import OpenLoop  # noqa: E402

PAGE = 16


def build_engine(cfg: dict, seed: int, device, node: int = 0):
    """One engine on ``device``: the program's model of the configuration's
    architecture, weights made from the seed."""
    from repro.models import build_model
    from repro.serving import EngineConfig, InferenceEngine
    arch = spec.arch(cfg["arch"])
    serving = cfg["serving"]
    model = build_model(arch.program_config(cfg))
    params = weights.make_params(cfg, seed, device, arch)
    weights.check_layout(params, model)
    return InferenceEngine(model, params, EngineConfig(
        max_slots=serving["slots"], max_seq=serving["max_seq"],
        page_size=PAGE, n_pages=serving["max_seq"] * serving["slots"]
        // PAGE, control=serving["control"],
        mitigate=serving["mitigate"], node=node))


def build_engines(cfg: dict, seed: int, devices: list) -> list:
    """One engine per device, each with the same seed's weights."""
    return [build_engine(cfg, seed, d, node=i) for i, d in enumerate(devices)]


def open_loop(engines: list, mix: dict, seed: int, spans: bool = False):
    """The open loop over the engines, behind the mix's router where
    there are several."""
    return OpenLoop(engines, spans=spans, router=mix.get("router"),
                    seed=seed)


class CompileCounter:
    """Counts programs lowered (compiled or loaded from the cache) while
    ``on`` is set: inside the window there should be none."""

    def __init__(self) -> None:
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self.on and event.endswith("jaxpr_to_mlir_module_duration"):
            self.n += 1


def sample(requests: list, seed: int, k: int) -> list:
    """k finished requests drawn from the seed: the longest, one of every
    replica that served any, and the rest at random."""
    done = [r for r in requests if r.finished]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), -r.rid))
    rest = [r for r in done if r is not longest]
    random.Random(seed ^ 0x5EED).shuffle(rest)
    picked = [longest]
    for rep in sorted({r.replica for r in rest} - {longest.replica}):
        picked.append(next(r for r in rest if r.replica == rep))
    chosen = {id(r) for r in picked}
    more = [r for r in rest if id(r) not in chosen]
    return picked + more[:max(k - len(picked), 0)]


def judge(gap: float, unanswered: int, rows: int, limit) -> tuple:
    """``correct`` and each compared number beside its limit."""
    checks = {
        "widest_gap": {"value": gap, "limit": limit},
        "unanswered": {"value": unanswered, "limit": 0},
        "compared_tokens": {"value": rows, "limit": 1},
    }
    ok = (limit is not None and gap <= limit and unanswered == 0
          and rows >= 1)
    return bool(ok), checks


def consumed(r, bucket: int) -> list[int]:
    """The tokens the engine fed: the prompt left-padded with token 0 into
    its bucket, then every served token but the last."""
    return [0] * (bucket - len(r.prompt)) + r.prompt + r.tokens[:-1]


def compare(cfg: dict, seed: int, picked: list, buckets: dict, device,
            control: bool = False) -> dict:
    """Widest reference gap of the served tokens (and of the fp8 control's
    first choices, with ``control``) over the sampled requests."""
    ref = cfg["reference"]
    length = cfg["serving"]["max_seq"]
    model = reference.Reference(cfg, seed, block=ref["block"],
                                length=length, device=device)
    seqs = [(consumed(r, buckets[r.rid]), r.tokens) for r in picked]
    out = {"served": 0.0, "rows": sum(len(s) for _, s in seqs)}
    if control:
        out["control"] = 0.0
    for toks, tgts in reference.pack(seqs, ref["block"], length):
        out["served"] = max(out["served"],
                            float(model.block_gaps(toks, tgts).max()))
        if control:
            out["control"] = max(out["control"], float(
                model.block_gaps(toks, tgts, control=True).max()))
    return out


def use_compile_cache() -> None:
    """JAX's persistent cache in ``<checkout>/.jax_cache`` (or
    ``$JAX_COMPILATION_CACHE_DIR``), small programs included, so only a
    checkout's first run compiles."""
    from repro.launch.serve import enable_compile_cache
    enable_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def log_monitor(engines: list, log) -> None:
    for i, eng in enumerate(engines):
        tel = eng.plane.report() if eng.plane is not None else {}
        who = f" replica {i}:" if len(engines) > 1 else ""
        log(f"[monitor]{who} {tel.get('events', 0)} events, findings "
            f"{tel.get('findings_by_row', {})}, actions "
            f"{[a for _, a, _ in tel.get('actions', [])]}")


def peak_memory(devices: list) -> list[int]:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices]


def run_cell(cell: dict, cfg: dict, mix: dict, *, seed: int, seconds: float,
             trace: bool, devices: list, bench: dict, t_start: float,
             control: bool = False, keep_trace: str | None = None,
             log=print) -> dict:
    """One run; returns the result object (the last line of stdout).
    ``devices`` holds at least the cell's chips; the first ``chips`` serve.

    With ``control`` the fp8-weight reference is put in the served path's
    place: its first choices are judged against the same limit by the
    same rule, so ``correct`` has to come out false; the served path's
    own judgement is kept under ``served``."""
    devices = list(devices[:cell["chips"]])
    device = devices[0]
    kind = device.device_kind
    peak = spec.peaks(kind) if device.platform == "tpu" else {}
    t_devices = time.perf_counter() - t_start
    engines = build_engines(cfg, seed, devices)
    jax.block_until_ready([e.params for e in engines])
    t_engine = time.perf_counter() - t_start
    loop = open_loop(engines, mix, seed, spans=trace)
    lens = traffic.prompt_lengths(mix, seconds)
    buckets = sorted({engines[0].sched.bucket_len(n) for n in lens})
    loop.warm_up(buckets)
    log(f"[setup] devices and imports by {t_devices:.3f} s, weights and "
        f"engine by {t_engine:.3f} s, warm-up of prefill buckets "
        f"{buckets} and decode by {time.perf_counter() - t_start:.3f} s")
    vocab = cfg["vocab_size"]
    requests = traffic.generate(mix, seed, seconds, vocab)
    counter = CompileCounter()
    holder = None
    if trace:
        last = mix.get("trace_seconds")
        part = (seconds - last, seconds) if last and last < seconds \
            else None
        with xtrace.traced(keep_trace, part) as holder:
            counter.on = True
            setup_s = time.perf_counter() - t_start
            rec = loop.run(requests, seconds, mix["drain"])
            counter.on = False
            t = time.perf_counter()
        log(f"[trace] {holder.summary.window_s:.3f} s traced on "
            f"{holder.summary.devices} devices; stopped and read in "
            f"{time.perf_counter() - t:.3f} s")
    else:
        counter.on = True
        setup_s = time.perf_counter() - t_start
        rec = loop.run(requests, seconds, mix["drain"])
        counter.on = False
    summary = holder.summary if holder is not None else None
    memory = peak_memory(devices)
    run = stats.Run(record=rec, setup_s=setup_s, config=cfg, peak=peak,
                    trace=summary, chips=len(devices))
    kind_key = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics(bench, cell["name"], kind_key):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    due = [r for r in rec.requests if r.due < seconds]
    attempted = due if mix["drain"] == "first_token" \
        else [r for r in due if r.tokens]
    unanswered = sum(1 for r in attempted if not r.tokens)
    late = sorted(r.submitted - r.due for r in rec.requests
                  if r.submitted >= 0)
    log(f"[window] {len(due)} requests due, {len(attempted)} attempted, "
        f"{sum(r.finished for r in rec.requests)} finished, "
        f"{stats.tokens_in_window(rec)} tokens in {seconds} s, "
        f"{len(rec.steps)} decode steps, loop stopped at {rec.stop:.3f} s")
    first = stats.ttfts(rec)
    log(f"[window] samples: {len(first)} first tokens (p50 "
        f"{(stats.quantile(first, 0.5) or 0) * 1e3:.3f} ms, p90 "
        f"{(stats.quantile(first, 0.9) or 0) * 1e3:.3f} ms), "
        f"{len(stats.token_gaps(rec))} token gaps")
    if late:
        log(f"[window] generator lateness: median "
            f"{stats.quantile(late, 0.5) * 1e3:.3f} ms, p99 "
            f"{stats.quantile(late, 0.99) * 1e3:.3f} ms, max "
            f"{late[-1] * 1e3:.3f} ms")
    log(f"[window] longest loop iteration, ms by phase: "
        f"{ {k: round(v * 1e3, 3) for k, v in rec.longest.items()} }; "
        f"longest garbage-collector pause {rec.gc_pause_s * 1e3:.3f} ms")
    log(f"[window] programs lowered inside the window: {counter.n}")
    log(f"[window] engine counters: {rec.counters}")
    log_monitor(engines, log)
    if len(engines) > 1:
        routed = [sum(r.replica == i for r in rec.requests)
                  for i in range(len(engines))]
        log(f"[replicas] devices: {[str(e.device) for e in engines]}")
        log(f"[replicas] requests routed: {routed}, decode steps: "
            f"{[c.get('steps', 0) for c in rec.replica_counters]}, "
            f"tokens: {[c.get('tokens', 0) for c in rec.replica_counters]}")
        log(f"[replicas] peak memory, bytes: {memory}")

    picked = sample(rec.requests, seed, cfg["reference"]["k"])
    buckets_by_id = dict(loop.buckets)
    del loop, engines, run
    gc.collect()
    t = time.perf_counter()
    gaps = compare(cfg, seed, picked, buckets_by_id, device, control)
    log(f"[reference] {len(picked)} requests (replicas "
        f"{sorted({r.replica for r in picked})}), {gaps['rows']} served "
        f"tokens compared in {time.perf_counter() - t:.2f} s")
    limit = cfg.get("gap_limit")
    served = judge(gaps["served"], unanswered, gaps["rows"], limit)
    correct, checks = served
    if control:
        log(f"[control] fp8-weight reference in the served path's place; "
            f"the served path read {gaps['served']!r}")
        correct, checks = judge(gaps["control"], unanswered, gaps["rows"],
                                limit)
    dev = {"platform": device.platform, "kind": kind,
           "count": jax.device_count(),
           "memory_peak_bytes": max(memory)}
    result = {"correct": correct, "attempted": len(attempted),
              "failed": unanswered, "metrics": metrics, "device": dev}
    if summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    if control:
        result["served"] = {"correct": served[0],
                            "widest_gap": gaps["served"]}
    result["checks"] = checks
    return result


def devices_for(cell: dict, name: str, tool: str):
    """The host's devices, or None after saying why they cannot serve the
    cell: no TPU, or fewer devices than its chips."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"{tool}: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return None
    if len(devices) < cell["chips"]:
        print(f"{tool}: {name} asks for {cell['chips']} chips, one replica "
              f"on each; this host has {len(devices)}", file=sys.stderr)
        return None
    return devices


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the trace, gzipped, to this file")
    args = ap.parse_args(argv)

    bench = spec.benchmark(ROOT)
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    devices = devices_for(cell, args.workload, "run")
    if devices is None:
        return 2
    spec.peaks(devices[0].device_kind)
    use_compile_cache()
    result = run_cell(cell, cfg, mix, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices,
                      bench=bench, t_start=T_START,
                      keep_trace=args.keep_trace)
    for name, c in result["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
