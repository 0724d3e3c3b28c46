"""The benchmark's general machinery, driven by ``BENCHMARK.json``.

A cell names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<traffic>.json``); the traffic names the plan
(``bench/plans/<plan>.py``), which builds the system under test and its plain
reference.  Each metric is a reader ``bench/metrics/<metric>.py`` with
``read(facts) -> float | None``; each cell's limits for the comparison that
decides ``correct`` are ``bench/limits/<workload>.json``.  Adding a cell,
mix, configuration or metric adds files; nothing here changes.

One run: build, record the samplers' fragments for the comparison, warm up
with whole ``Algorithm.train()`` iterations, measure ``train()`` back to back
for the window (whole cycles of the plan, at least ``--seconds``), free the
program, then compare what set-up recorded of the timed path (each sampler's
fragments through its own ``sample()``, the first three learner steps) with
the reference.
"""

from __future__ import annotations

import gc
import hashlib
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = ROOT / ".bench"
HOST_COLUMNS = ("batch_indices", "eps_id")
LEARN_STEPS = 3  # learner steps the reference follows
MISSING = 1e30  # the reading of a number that could not be made


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def program_seed(seed: int) -> int:
    """The program keys worker ``i`` with ``seed * 10007 + i`` as a 32-bit
    integer; map any benchmark seed into that range, deterministically."""
    h = hashlib.sha256(str(int(seed)).encode()).digest()
    return int.from_bytes(h[:8], "big") % 200_000


def load_file_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_metric_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The reader of ``metric``: ``bench/metrics/<metric>.py``, else the file of
    the longest dotted prefix of the name (``learner.device_ms.lm`` is read by
    ``learner.device_ms.py`` where no file of its own exists)."""
    parts = metric.split(".")
    for n in range(len(parts), 0, -1):
        path = BENCH / "metrics" / (".".join(parts[:n]) + ".py")
        if path.is_file():
            return load_file_module(path)
    raise SystemExit(f"bench: no reader for metric {metric!r} in bench/metrics/")


class Cell:
    """A workload of ``BENCHMARK.json`` with its files resolved."""

    def __init__(self, bench: dict, name: str, model: Optional[dict] = None,
                 traffic: Optional[dict] = None, limits: Optional[dict] = None):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
        self.bench, self.name, self.spec = bench, name, cells[name]
        cfg = {c["name"]: c for c in bench["configs"]}[self.spec["config"]]
        self.model = model or load_json(ROOT / cfg["file"])
        self.traffic = traffic or load_json(BENCH / "traffic" / f"{self.spec['traffic']}.json")
        self.limits = limits if limits is not None else load_json(BENCH / "limits" / f"{name}.json")
        self.plan = importlib.import_module(f"bench.plans.{self.traffic['plan']}")
        self.chips = int(self.spec["chips"])

    def metrics(self, trace: bool) -> List[dict]:
        """End-to-end metrics (trace 0) or per-layer ones (trace 1) of the cell."""
        e2e = self.bench["end_to_end"]
        mine = {m["name"] for m in e2e if self.name in m.get("workloads", [self.name])}
        if not trace:
            return [m for m in e2e if m["name"] in mine]
        return [m for m in self.bench["per_layer"]
                if self.name in m.get("workloads", [self.name]) and m["moves"] in mine]


class CompileClock:
    """Counts XLA backend compiles and their seconds (JAX monitoring)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_: Any) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def lap(self):
        out = (self.count, self.seconds)
        self.count, self.seconds = 0, 0.0
        return out


def _leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    with jax.default_matmul_precision("highest"):
        return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
                for p, x in flat}


def _diff_norms(new, old_host) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    flat, _ = jax.tree_util.tree_flatten_with_path(new)
    old = dict((jax.tree_util.keystr(p), x) for p, x in jax.tree_util.tree_flatten_with_path(old_host)[0])
    out = {}
    for p, x in flat:
        k = jax.tree_util.keystr(p)
        d = x.astype(jnp.float32) - jnp.asarray(old[k], jnp.float32)
        out[k] = float(jnp.sqrt(jnp.sum(jnp.square(d))))
    return out


class Recorder:
    """Records, through the window's own calls, what the comparison needs:
    the fragments each sampler returns from ``sample()`` while ``collect``
    holds (set-up, before any learner step), and the first three learner
    steps (batch, loss, the gradient as Adam's first moment holds it after
    step one, and the parameters' change after step three).  Unhooks the
    learner once done; the samplers' hook only copies rows when asked to."""

    def __init__(self, ws, plan, adam_b1: float):
        self.plan, self.ws, self.adam_b1 = plan, ws, adam_b1
        self.lw = ws.local_worker()
        self.fragments: Dict[int, List[dict]] = {}
        self.collect = False
        self.steps: List[dict] = []
        self.grad_norms: Dict[str, float] = {}
        self.change_norms: Dict[str, float] = {}
        self.rows: Optional[List[dict]] = None
        self._p0 = None
        self._orig_learn = self.lw.learn_on_batch
        self.lw.learn_on_batch = self._learn
        for actor in ws.remote_workers().actors:
            target = actor.target
            target.sample = self._sampler(target, target.sample)

    def release(self) -> None:
        """Drop every hook and reference into the program, so that stopping
        it frees its device state before the reference runs."""
        if self.lw is not None and "learn_on_batch" in vars(self.lw):
            del self.lw.learn_on_batch
        for actor in self.ws.remote_workers().actors:
            vars(actor.target).pop("sample", None)
        self.lw = self.ws = self._p0 = None

    @property
    def done(self) -> bool:
        return len(self.steps) >= LEARN_STEPS

    def _sampler(self, target, orig: Callable):
        def sample(*a, **k):
            batch = orig(*a, **k)
            if self.collect:
                self.fragments.setdefault(int(target.worker_index), []).append(
                    {key: np.array(v) for key, v in batch.items()})
            if self.rows is not None:
                self.rows.append(self.plan.row_stats(batch))
            return batch

        return sample

    def record_fragments(self, n: int) -> None:
        """Each sampler's next ``n`` fragments, through its own ``sample()`` on
        its own thread, all samplers at once."""
        self.collect = True
        try:
            actors = self.ws.remote_workers().actors
            for _ in range(n):
                for fut in [a.call("sample") for a in actors]:
                    fut.result()
        finally:
            self.collect = False

    def _learn(self, batch, *a, **k):
        import jax

        i = len(self.steps)
        if i == 0:
            self._p0 = jax.device_get(self.lw.params)
        info = self._orig_learn(batch, *a, **k)
        self.steps.append({
            "batch": {key: np.array(v) for key, v in batch.items() if key not in HOST_COLUMNS},
            "loss": float(info["loss"]),
        })
        if i == 0:  # Adam's first moment after one step is (1 - b1) x gradient
            self.grad_norms = _leaf_norms(jax.tree_util.tree_map(
                lambda m: m / (1.0 - self.adam_b1), self.lw.opt_state.mu))
        if i + 1 == LEARN_STEPS:
            self.change_norms = _diff_norms(self.lw.params, self._p0)
            self._p0 = None
            del self.lw.learn_on_batch  # back to the class's method
        return info


# ------------------------------------------------------------------ readings
def _rms(x) -> float:
    x = np.asarray(x, np.float64)
    return float(np.sqrt(np.mean(x * x))) or 1e-30


def reference_outputs(ref, rec: Recorder, m: dict, t: dict) -> dict:
    """What the reference says of the recorded fragments and steps."""
    import jax

    from bench.reference import rl as ref_rl

    out: dict = {"rollout": {}, "post": {}, "loss": [], "grad": {}, "change": {}}
    for widx, frags in sorted(rec.fragments.items()):
        p = ref.init(widx)
        logp, v, post = ref.rollout(p, frags)
        out["rollout"][widx] = (logp, v)
        if post is not None:
            out["post"][widx] = post
        del p
    p0 = ref.cast(ref.init(0))  # the control stores its parameters in bfloat16
    p, st = p0, ref_rl.adam_init(p0)
    for i, step in enumerate(rec.steps[:LEARN_STEPS]):
        loss, g = ref.loss_and_grad(p, step["batch"])
        out["loss"].append(float(loss))
        if i == 0:
            out["grad"] = _leaf_norms(g)
        p, st = ref_rl.adam_step(p, g, st, t["lr"], m["optimizer"])
    out["change"] = _diff_norms(p, jax.device_get(p0))
    return out


def program_outputs(rec: Recorder) -> dict:
    out: dict = {"rollout": {}, "post": {}, "loss": [s["loss"] for s in rec.steps[:LEARN_STEPS]],
                 "grad": rec.grad_norms, "change": rec.change_norms}
    for widx, frags in sorted(rec.fragments.items()):
        def cat(key):
            return np.concatenate([f[key] for f in frags])

        out["rollout"][widx] = (cat("logp"), cat("values"))
        if "advantages" in frags[0]:
            out["post"][widx] = (cat("advantages"), cat("returns"))
    return out


def leaf_gaps(cand: Dict[str, float], want: Dict[str, float], keep=None) -> Dict[str, float]:
    """Per leaf, |cand - want| / max(want, median of want); a leaf missing on
    either side reads inf."""
    if set(cand) != set(want) or not want:
        return {"(leaves differ)": math.inf}
    med = float(np.median(list(want.values())))
    return {k: abs(cand[k] - want[k]) / max(want[k], med, 1e-30)
            for k in want if keep is None or k in keep}


def compare(cand: dict, want: dict, detail: bool = False) -> Dict[str, float]:
    """The numbers compared (each named as in the limits files)."""
    r: Dict[str, float] = {}
    lp, vg = [], []
    for widx, (wl, wv) in want["rollout"].items():
        if widx not in cand["rollout"]:
            return {"rollout.logp_gap": MISSING}
        cl, cv = cand["rollout"][widx]
        lp.append(float(np.max(np.abs(np.asarray(cl, np.float64) - wl))))
        vg.append(float(np.max(np.abs(np.asarray(cv, np.float64) - wv))) / _rms(wv))
    r["rollout.logp_gap"] = max(lp) if lp else math.inf
    r["rollout.value_gap"] = max(vg) if vg else math.inf
    if want["post"]:
        ag = []
        for widx, (wa, _) in want["post"].items():
            ca = cand["post"].get(widx, (np.full_like(wa, np.nan), None))[0]
            ag.append(float(np.max(np.abs(np.asarray(ca, np.float64) - wa))) / _rms(wa))
        r["postprocess.advantage_gap"] = max(ag)
    if len(cand["loss"]) != LEARN_STEPS or len(want["loss"]) != LEARN_STEPS:
        r["learner.loss_gap"] = math.inf
    else:
        r["learner.loss_gap"] = max(abs(c - w) / max(abs(w), 1e-30)
                                    for c, w in zip(cand["loss"], want["loss"]))
    # Leaves whose reference gradient is nought to rounding (under a
    # thousandth of the median leaf's) move under Adam by round-off alone.
    med = float(np.median(list(want["grad"].values()))) if want["grad"] else 0.0
    moving = {k for k, g in want["grad"].items() if g >= 1e-3 * med}
    for name, key, keep in (("grad", "grad", None), ("change", "change", moving)):
        gaps = list(leaf_gaps(cand[key], want[key], keep).values()) or [math.inf]
        r[f"learner.{name}_gap"] = max(gaps)
        r[f"learner.{name}_gap_median"] = float(np.median(gaps))
    if detail:
        r["leaves"] = {"grad": leaf_gaps(cand["grad"], want["grad"]),
                       "change": leaf_gaps(cand["change"], want["change"], moving)}
        return r
    # A reading that cannot be made (nan, a missing leaf) is a failing one;
    # 1e30 keeps the printed result valid JSON.
    return {k: (v if math.isfinite(v) else MISSING) for k, v in r.items()}


def readings(cell: Cell, seed: int, rec: Recorder, control: bool = False,
             detail: bool = False) -> Dict[str, float]:
    """Program against the float32 reference; with ``control``, the
    bfloat16 reference put in the program's place."""
    import jax
    import jax.numpy as jnp

    pseed = program_seed(seed)
    with jax.default_matmul_precision("highest"):
        want = reference_outputs(cell.plan.Reference(cell.model, cell.traffic, pseed), rec,
                                 cell.model, cell.traffic)
    if control:
        cand = reference_outputs(
            cell.plan.Reference(cell.model, cell.traffic, pseed, dtype=jnp.bfloat16), rec,
            cell.model, cell.traffic)
    else:
        cand = program_outputs(rec)
    return compare(cand, want, detail)


# ---------------------------------------------------------------------- run
def configure_jax() -> str:
    """Persistent compile cache inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), caching every program however small."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def device_info(chips: int, allow_cpu: bool = False) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise SystemExit(f"bench: needs a TPU, found {devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def peak_for(kind: str) -> dict:
    """The chip's published peaks (``bench/peaks.json``); an unknown device
    is an error, not a default."""
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in bench/peaks.json")
    return peaks[kind]


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats()
        if st and "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def stop(algo, ws, rec: Optional[Recorder] = None) -> None:
    try:
        algo.stop()
    finally:
        ws.stop()
        if rec is not None:
            rec.release()


def setup(cell: Cell, seed: int, fault: Optional[Callable] = None):
    """Build the cell's system, hook the recorder, plant ``fault`` (tests
    and the control runs only), and warm up.  Returns (algo, ws, rec, res)."""
    algo, ws = cell.plan.build(cell.model, cell.traffic, program_seed(seed))
    rec = Recorder(ws, cell.plan, cell.model["optimizer"]["b1"])
    if fault is not None:
        fault(ws, cell)
    res = None
    try:
        rec.record_fragments(cell.plan.check_fragments(cell.traffic))
        for _ in range(int(cell.traffic["warmup_iters"])):
            res = algo.train()
        deadline = time.monotonic() + 120
        while not rec.done and time.monotonic() < deadline:
            res = algo.train()
    except BaseException:
        stop(algo, ws, rec)
        raise
    return algo, ws, rec, res


def trained(res) -> int:
    return int(res["counters"].get("num_steps_trained", 0)) if res else 0


def window(algo, ws, seconds: float, trace_dir: Optional[str], cycle: int = 1):
    """``train()`` back to back until ``seconds`` have passed and the
    iterations done are a whole number of ``cycle``s.  Returns the window's
    facts."""
    import jax

    attempted = failed = 0
    res = None
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    ann = jax.profiler.TraceAnnotation
    try:
        with ann("bench.window"):
            t0 = time.perf_counter()
            while True:
                attempted += 1
                try:
                    with ann("bench.train"):
                        res = algo.train()
                except Exception as exc:  # counted, and the window ends
                    log(f"train() raised in the window: {exc!r}")
                    failed += 1
                    break
                loss = res.get("info", {}).get("loss", 0.0)
                if not isinstance(loss, (int, float)) or not math.isfinite(float(loss)):
                    failed += 1
                if time.perf_counter() - t0 >= seconds and attempted % cycle == 0:
                    break
            t1 = time.perf_counter()
    finally:
        if trace_dir:
            t2 = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"trace: stop_trace {time.perf_counter() - t2:.1f} s")
    dropped = sum(int(getattr(a.target, "num_fragments_dropped", 0))
                  for a in ws.remote_workers().actors)
    return {"attempted": attempted, "failed": failed + dropped, "t0": t0, "t1": t1, "res": res}


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        allow_cpu: bool = False, fault: Optional[Callable] = None) -> dict:
    """One run of a cell; returns the result object (and prints nothing)."""
    import jax

    device = device_info(cell.chips, allow_cpu)
    # On the CPU (tests only) the v5e's peaks stand in.
    chip_peaks = peak_for("TPU v5 lite" if allow_cpu and device["platform"] == "cpu" else device["kind"])
    clock = CompileClock()
    algo, ws, rec, res = setup(cell, seed, fault)
    try:
        before = trained(res)
        compiles_setup = clock.lap()
        trace_dir = str(OUT / "trace" / cell.name) if trace else None
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)  # one trace per cell on disk
            rec.rows = []
        setup_s = time.perf_counter() - t_start
        w = window(algo, ws, seconds, trace_dir, cell.plan.cycle_iterations(cell.traffic))
        compiles_window = clock.lap()
        units = trained(w["res"]) - before
        mem_peak = memory_peak(cell.chips)
    finally:
        stop(algo, ws, rec)
    rows, rec.rows = rec.rows, None
    del algo, ws, res, w["res"]
    gc.collect()

    window_s = w["t1"] - w["t0"]
    facts = {
        "cell": cell.spec, "model": cell.model, "traffic": cell.traffic, "chips": cell.chips,
        "peak": chip_peaks,
        "setup_s": setup_s, "window_s": window_s, "units": units,
        "units_per_s": units / window_s if window_s > 0 else 0.0,
        "iterations": w["attempted"], "memory_peak_bytes": mem_peak, "trace": None,
        "rows": rows,
    }
    t2 = time.perf_counter()
    checks = readings(cell, seed, rec)
    log(f"reference: {time.perf_counter() - t2:.1f} s")
    out_device = dict(device, memory_peak_bytes=mem_peak)
    breakdown = None
    if trace:
        from bench import trace as tr

        path = tr.find_xplane(trace_dir)
        if path:
            t2 = time.perf_counter()
            reduced = tr.reduce_trace(path, n_devices=cell.chips)
            log(f"trace: reduce {time.perf_counter() - t2:.1f} s")
            facts["trace"] = reduced
            out_device["busy_s"] = reduced["busy_s"]
            out_device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": tr.top_ops(reduced), "idle_gaps": reduced["idle_gaps"]}
    metrics = {}
    for m in cell.metrics(trace):
        v = reader(m["name"]).read(facts)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    limits = cell.limits
    compared = {k: {"value": checks.get(k, MISSING), "limit": lim} for k, lim in limits.items()}
    correct = bool(limits) and all(c["value"] <= c["limit"] for c in compared.values()) \
        and w["failed"] == 0 and units > 0
    result = {
        "correct": correct, "attempted": w["attempted"], "failed": w["failed"],
        "metrics": metrics, "device": out_device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compiles"] = {"setup": compiles_setup[0], "setup_s": compiles_setup[1],
                          "window": compiles_window[0], "window_s": compiles_window[1]}
    result["readings"] = checks
    result["checks"] = compared
    return result
