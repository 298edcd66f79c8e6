"""The benchmark harness: everything is found by name from ``BENCHMARK.json``.

A cell names a configuration (``configs/<config>.json``, its ``file`` in
``BENCHMARK.json``) and a traffic mix (``traffic/<traffic>.json``); each
metric is a reader ``metrics/<name>.py``. One general driver (``Driver``)
runs every mix: a mix is a tick of data, and a tick may rebuild the index
from a fresh object set, stage moves of the fleet, answer query batches, and
flush. So a new cell, configuration, mix or metric is new files plus
entries, and no file here changes.

Timed path, through the library's public surface only: ``build_knn_tables_jax
(..., use_pallas=False, plans=...)``, ``QueryEngine.query_batch`` with the
answers read back to the host, ``stage_move`` then ``flush_updates`` ended
by ``block_until_ready``. The engine runs its defaults (the XLA path).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_SECONDS = 10.0


class BenchError(RuntimeError):
    """The run cannot give a result (no chip, an unknown name, bad data)."""


# ---------------------------------------------------------------------------
# discovery by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]   # the metrics this cell reports with --trace 0
    per_layer: list[dict]    # ... and with --trace 1


def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.exists():
        raise BenchError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def resolve(bench: dict, workload: str, root: Path = ROOT) -> Cell:
    """The cell ``workload`` with its configuration, mix and metrics, each
    read from its own file under the checkout ``root``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((Path(root) / configs[w["config"]]["file"]).read_text())
    traffic_file = Path(root) / HERE.relative_to(ROOT) / "traffic" / f"{w['traffic']}.json"
    if not traffic_file.exists():
        raise BenchError(f"no traffic mix {traffic_file}")
    traffic = json.loads(traffic_file.read_text())
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    # a per-layer metric without ``workloads`` is read wherever its
    # end-to-end metric is reported
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload] if m["moves"] in reported else [])]
    return Cell(workload, int(w["chips"]), cfg, traffic, e2e, per_layer)


def load_metric(name: str, root: Path = ROOT):
    """The module of metric ``name``: its ``read(rec)``, and for a roofline
    share the op/byte function it divides by."""
    path = Path(root) / HERE.relative_to(ROOT) / "metrics" / f"{name}.py"
    if not path.exists():
        raise BenchError(f"no metric reader {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the run's record: what the readers read
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Record:
    cell: Cell
    k: int
    n: int
    setup_s: float = 0.0
    window_s: float = 0.0
    batch_lat_s: list[float] = dataclasses.field(default_factory=list)
    batch_size: list[int] = dataclasses.field(default_factory=list)
    dispatch_s: list[float] = dataclasses.field(default_factory=list)
    visible_s: list[float] = dataclasses.field(default_factory=list)
    flushes: list[dict] = dataclasses.field(default_factory=list)
    builds_s: list[float] = dataclasses.field(default_factory=list)
    moves: int = 0
    ticks: int = 0
    window_compiles: int = 0
    window_cache_hits: int = 0
    device_kind: str = ""
    sweep_entries: tuple[int, int] = (0, 0)   # valid schedule entries (up, down)
    trace: object | None = None        # devtrace.Summary of the traced window

    @property
    def queries(self) -> int:
        return int(sum(self.batch_size))


def percentile(values, weights, q: float) -> float | None:
    """Nearest-rank q-th percentile of ``values`` each counted ``weights`` times."""
    if not len(values):
        return None
    order = np.argsort(values, kind="stable")
    v = np.asarray(values, np.float64)[order]
    cum = np.cumsum(np.asarray(weights, np.float64)[order])
    return float(v[np.searchsorted(cum, q / 100.0 * cum[-1] - 1e-9)])


# ---------------------------------------------------------------------------
# traffic: one general generator over the mix's data
# ---------------------------------------------------------------------------


class Traffic:
    """Query origins Zipf(theta) over a seeded permutation of the vertices
    (YCSB's request distribution), each query's k uniform in [k_min, k_max],
    and a fleet's collision-free random walk: each tick a seeded order of
    vehicles, each moving one street to a free neighbor, until the moves
    are ``update_share`` of the tick's operations (its queries and moves),
    as YCSB counts a mix's updates."""

    def __init__(self, mix: dict, g, k: int, seed: int, stream: int):
        self.tick = mix["tick"]
        self.g = g
        self.rng = np.random.default_rng([seed, stream])
        o = self.tick.get("origins")
        if o:
            if o["dist"] != "zipf":
                raise BenchError(f"unknown origin distribution {o['dist']!r}")
            self.perm = np.random.default_rng([seed, 7]).permutation(g.n).astype(np.int32)
            p = 1.0 / np.arange(1, g.n + 1, dtype=np.float64) ** float(o["theta"])
            self.cdf = np.cumsum(p) / p.sum()
        kr = self.tick.get("k", {})
        self.k_min = int(kr.get("min", 1))
        self.k_max = k if kr.get("max", "index") == "index" else int(kr["max"])

    def batch(self) -> tuple[np.ndarray, np.ndarray]:
        b = int(self.tick["batch"])
        idx = np.minimum(np.searchsorted(self.cdf, self.rng.random(b)), self.g.n - 1)
        return self.perm[idx], self.rng.integers(self.k_min, self.k_max + 1, size=b,
                                                 dtype=np.int32)

    def moves_per_tick(self, fleet: int) -> int:
        share = float(self.tick.get("update_share", 0.0))
        reads = int(self.tick.get("query_batches", 0)) * int(self.tick.get("batch", 0))
        return min(fleet, int(round(share / (1.0 - share) * reads)))

    def moves(self, pos: list[int], occupied: set[int]) -> list[tuple[int, int]]:
        want = self.moves_per_tick(len(pos))
        out: list[tuple[int, int]] = []
        g = self.g
        for i in self.rng.permutation(len(pos)).tolist():
            if len(out) == want:
                break
            u = pos[i]
            s, e = int(g.indptr[u]), int(g.indptr[u + 1])
            v = int(g.indices[s + int(self.rng.integers(e - s))])
            if v in occupied:
                continue
            occupied.discard(u)
            occupied.add(v)
            pos[i] = v
            out.append((u, v))
        return out


def draw_objects(cfg: dict, n: int, rng) -> np.ndarray:
    size = int(cfg["fleet_size"]) if cfg["objects"] == "fleet" else max(1, round(cfg["mu"] * n))
    return np.sort(rng.choice(n, size=size, replace=False)).astype(np.int32)


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------


class Spans:
    """Host spans of the harness: recorded on the host clock, and written
    into the profiler's trace as ``bench:<name>`` when it runs."""

    def __init__(self, traced: bool):
        self.traced = traced

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self.traced:
            import jax

            with jax.profiler.TraceAnnotation("bench:" + name):
                yield
        else:
            yield


@dataclasses.dataclass
class Checked:
    """What the window produced, kept to compare once it has closed."""

    epochs: list[np.ndarray] = dataclasses.field(default_factory=list)
    answers: list[tuple] = dataclasses.field(default_factory=list)   # (epoch, u, kq, ids, d)


class Driver:
    def __init__(self, cell: Cell, seed: int, *, cache_dir: Path, traced: bool):
        import jax

        from network import load_network
        from repro.core.construct_jax import prepare_sweep

        self.jax = jax
        self.cell = cell
        cfg = cell.config
        self.g, self.bn = load_network(cfg["network"], cache_dir)
        if self.g.n != int(cfg["vertices"]):
            raise BenchError(f"{cfg['name']}: network has {self.g.n} vertices, "
                             f"config says {cfg['vertices']}")
        self.k = int(cfg["k"])
        self.tick = cell.traffic["tick"]
        self.plans = (prepare_sweep(self.bn, "up"), prepare_sweep(self.bn, "down"))
        self.rec = Record(cell=cell, k=self.k, n=self.g.n,
                          device_kind=jax.devices()[0].device_kind,
                          sweep_entries=(int((self.bn.lo_ids >= 0).sum()),
                                         int((self.bn.hi_ids >= 0).sum())))
        self.spans = Spans(traced)
        self.obj_rng = np.random.default_rng([seed, 1])
        self.check_rng = np.random.default_rng([seed, 2])
        self.warm = Traffic(cell.traffic, self.g, self.k, seed, 3)
        self.live = Traffic(cell.traffic, self.g, self.k, seed, 4)
        self.checked = Checked()
        self.engine = None
        self.tables = None
        objects = draw_objects(cfg, self.g.n, self.obj_rng)
        self.pos = objects.tolist()
        self.occupied = set(self.pos)
        if not self.tick.get("rebuild"):
            from repro.core.engine import QueryEngine

            ids, d = self.build(objects)
            self.engine = QueryEngine(ids, d, self.k, objects, bn=self.bn)
        self.checked.epochs.append(objects)

    def build(self, objects: np.ndarray):
        from repro.core.construct_jax import build_knn_tables_jax

        tables = build_knn_tables_jax(self.bn, objects, self.k, use_pallas=False,
                                      plans=self.plans)
        return self.jax.block_until_ready(tables)

    def run_tick(self, traffic: Traffic, rec: Record | None) -> None:
        """One tick of the mix. ``rec`` None is warm-up: nothing is kept."""
        jax = self.jax
        tick = self.tick
        sp = self.spans
        now = time.perf_counter
        if tick.get("rebuild"):
            objects = draw_objects(self.cell.config, self.g.n, self.obj_rng)
            with sp("build"):
                t0 = now()
                self.tables = self.build(objects)
                t1 = now()
            size = int(cell_check(self.cell, "rows_per_build"))
            rows = self.check_rng.choice(self.g.n, size=size, replace=False).astype(np.int32)
            with sp("sample_rows"):
                r = jax.device_put(rows)
                ids = np.asarray(self.tables[0][r])
                d = np.asarray(self.tables[1][r])
            if rec is not None:
                rec.builds_s.append(t1 - t0)
                self.checked.epochs.append(objects)
                e = len(self.checked.epochs) - 1
                for j, u in enumerate(rows.tolist()):
                    self.checked.answers.append((e, u, self.k, ids[j], d[j]))
        ack: list[float] = []
        if tick.get("update_share"):
            with sp("traffic_gen"):
                moves = traffic.moves(self.pos, self.occupied)
            with sp("stage_moves"):
                for u, v in moves:
                    self.engine.stage_move(u, v)
                    ack.append(now())
        for _ in range(int(tick.get("query_batches", 0))):
            with sp("traffic_gen"):
                us, ks = traffic.batch()
                p = int(self.check_rng.integers(len(us)))
            with sp("query_batch"):
                t0 = now()
                ids, d = self.engine.query_batch(us, ks)
                t1 = now()
            with sp("readback"):
                ids = np.asarray(ids)
                d = np.asarray(d)
                t2 = now()
            if rec is not None:
                rec.batch_lat_s.append(t2 - t0)
                rec.dispatch_s.append(t1 - t0)
                rec.batch_size.append(len(us))
                self.checked.answers.append((len(self.checked.epochs) - 1, int(us[p]),
                                             int(ks[p]), ids[p].copy(), d[p].copy()))
        if tick.get("flush"):
            before = self.engine.stats()["t_repair_s"]
            with sp("flush"):
                t_flush = now()
                res = self.engine.flush_updates()
                jax.block_until_ready(self.engine.tables)
                t_pub = now()
            if rec is not None:
                res["flush_s"] = t_pub - t_flush
                rec.visible_s.extend(t_pub - a for a in ack)
                rec.moves += len(ack)
                res["repair_s"] = self.engine.stats()["t_repair_s"] - before
                rec.flushes.append(res)
            self.checked.epochs.append(np.sort(np.asarray(self.pos, np.int32)))
        if rec is not None:
            rec.ticks += 1

    def warm_up(self) -> list[int]:
        """Ticks of the cell's own traffic until ``quiet_ticks`` in a row
        neither compile a program nor load one from the persistent cache (at
        least ``min_ticks``, at most ``max_ticks``); returns each tick's
        count of programs compiled or loaded."""
        from repro.analysis import sanitize

        w = self.cell.traffic["warmup"]
        quiet = 0
        per_tick: list[int] = []
        while len(per_tick) < int(w["max_ticks"]) and (len(per_tick) < int(w["min_ticks"])
                                                       or quiet < int(w["quiet_ticks"])):
            with sanitize.count_compiles() as c:
                self.run_tick(self.warm, None)
            # ``count`` includes the programs served from the persistent cache
            per_tick.append(c.count)
            quiet = quiet + 1 if c.count == 0 else 0
        # warm-up answers are not compared: drop what the ticks kept
        self.checked.answers.clear()
        self.checked.epochs[:] = self.checked.epochs[-1:]
        return per_tick

    def window(self, seconds: float) -> Record:
        """Whole ticks until ``seconds`` have passed; the window ends with
        the tick that crosses it."""
        from repro.analysis import sanitize

        rec = self.rec
        with sanitize.count_compiles() as c, self.spans("window"):
            t0 = time.perf_counter()
            while True:
                self.run_tick(self.live, rec)
                if time.perf_counter() - t0 >= seconds:
                    break
            rec.window_s = time.perf_counter() - t0
        rec.window_compiles = c.count
        rec.window_cache_hits = c.cache_hits
        return rec


def peak(device_kind: str, key: str) -> float:
    """A published peak of the device, from ``peaks.json``; an unknown
    device is an error, not a default."""
    peaks = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in peaks:
        raise BenchError(f"no published peaks for device kind {device_kind!r}")
    return float(peaks[device_kind][key])


def cell_check(cell: Cell, key: str):
    return cell.traffic["check"][key]


# ---------------------------------------------------------------------------
# correctness: the window's answers against the plain reference
# ---------------------------------------------------------------------------


def sample_answers(checked: Checked, seed: int, size: int) -> list[tuple]:
    rng = np.random.default_rng([seed, 5])
    n = len(checked.answers)
    pick = rng.choice(n, size=min(size, n), replace=False) if n else []
    return [checked.answers[i] for i in sorted(pick)]


def compare_answers(g, checked: Checked, sample: list[tuple]) -> tuple[int, list[str]]:
    import reference

    wrong, notes = 0, []
    masks: dict[int, np.ndarray] = {}
    for e, u, kq, ids, d in sample:
        if e not in masks:
            m = np.zeros(g.n, bool)
            m[checked.epochs[e]] = True
            masks[e] = m
        why = reference.compare(g, masks[e], kq, u, ids, d)
        if why is not None:
            wrong += 1
            if len(notes) < 3:
                notes.append(f"epoch {e} u={u} k={kq}: {why}")
    return wrong, notes


def control_answers(g, checked: Checked, sample: list[tuple], width: int) -> list[tuple]:
    """The control in the program's place: the reference in bfloat16."""
    import reference

    out = []
    for e, u, kq, _, _ in sample:
        m = np.zeros(g.n, bool)
        m[checked.epochs[e]] = True
        ids, d = reference.answer(g, m, kq, u, width, precision="bfloat16")
        out.append((e, u, kq, ids, d))
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak(jax, chips: int) -> int:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run(workload: str, seed: int, seconds: float, trace: bool, *, t_start: float,
        root: Path = ROOT, cache_dir: Path | None = None, require_tpu: bool = True,
        overrides: dict | None = None, log=print) -> dict:
    """One run of one cell; returns the result line's object."""
    import jax

    from repro.analysis import sanitize

    bench = load_benchmark(root)
    cell = resolve(bench, workload, root)
    for key, value in (overrides or {}).items():
        override(cell, key, value)
    info = device_info(jax)
    if require_tpu and info["platform"] != "tpu":
        raise BenchError(f"needs a TPU; JAX found {info['platform']}")
    if info["count"] < cell.chips:
        raise BenchError(f"{workload} needs {cell.chips} chips; JAX found {info['count']}")
    cache_dir = Path(cache_dir) if cache_dir else HERE / ".cache"
    readers = {m["name"]: load_metric(m["name"], root).read
               for m in (cell.per_layer if trace else cell.end_to_end)}

    with sanitize.count_compiles() as setup_compiles:
        drv = Driver(cell, seed, cache_dir=cache_dir, traced=trace)
        warm = drv.warm_up()
    log(f"setup_compiles: {setup_compiles.count} (cache hits {setup_compiles.cache_hits}), "
        f"warm-up ticks: {len(warm)}, programs per tick: {warm}")

    drv.rec.setup_s = time.perf_counter() - t_start
    trace_dir = cache_dir / "trace"
    if trace:
        # the traced window is a short one of its own: at most TRACE_SECONDS,
        # with the profiler's Python tracer off
        seconds = min(seconds, TRACE_SECONDS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    rec = drv.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    log(f"window_compiles: {rec.window_compiles} (cache hits {rec.window_cache_hits}), "
        f"ticks: {rec.ticks}, queries: {rec.queries}, moves: {rec.moves}, "
        f"builds: {len(rec.builds_s)}, window_s: {rec.window_s}")
    if rec.flushes:
        log("flush_s: " + " ".join(f"{f['flush_s']:.3f}" for f in rec.flushes))
    info["memory_peak_bytes"] = memory_peak(jax, cell.chips)

    if trace:
        import devtrace

        rec.trace = devtrace.reduce(devtrace.events(devtrace.xplane_file(trace_dir)))
        log(f"trace programs: {json.dumps(rec.trace.programs_s)}")
        info["busy_s"] = rec.trace.busy_s
        info["window_s"] = rec.trace.window_s

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = readers[m["name"]](rec)
        if value is None:
            if not trace:
                raise BenchError(f"{workload}: end-to-end metric {m['name']} read nothing")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # the window has closed and the peak is read: free the program's state,
    # then hold the sampled answers to the plain reference
    g, checked = drv.g, drv.checked
    del drv
    sample = sample_answers(checked, seed, int(cell_check(cell, "answers")))
    t0 = time.perf_counter()
    wrong, notes = compare_answers(g, checked, sample)
    log(f"reference_s: {time.perf_counter() - t0}, compared: {len(sample)}")
    for note in notes:
        log(f"wrong: {note}")
    checks = {"wrong_answers": {"value": wrong, "limit": 0}}
    result = {
        "correct": bool(sample) and wrong == 0,
        "attempted": rec.queries + rec.moves + len(rec.builds_s),
        "failed": 0,
        "metrics": metrics,
        "device": info,
    }
    if trace:
        result["breakdown"] = {"device_ops": rec.trace.device_ops,
                               "idle_gaps": rec.trace.idle_gaps}
    result["checks"] = checks
    return result


def override(cell: Cell, key: str, value) -> None:
    """``a.b`` = value into the cell's config (``config.``) or mix (``traffic.``)."""
    top, *path = key.split(".")
    d = cell.config if top == "config" else cell.traffic
    for p in path[:-1]:
        d = d[p]
    d[path[-1]] = value


def emit(result: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error; the result object as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"{name}: {c['value']} (limit {c['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None, *, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(msg, flush=True)

    from repro.analysis import sanitize

    log(f"compile_cache_dir: {sanitize.enable_compile_cache()}")
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     t_start=t_start, log=log)
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    emit(result)
    return 0
