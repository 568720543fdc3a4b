"""The benchmark's loop, driven by ``BENCHMARK.json`` and the files it names.

A cell (one ``workloads`` entry) names a configuration and a traffic mix.
Everything that belongs to one of them is data in a file of its own:

* ``bench/configs/<config>.json``: the model's sizes as run, the
  deployment (nodes, fault tolerance, batch, ``Model`` options), the
  optimizer, the limits of the correctness check, and the files that
  belong to the configuration: its plain reference (``reference``), its
  FLOP count (``work``) and its test-sized copy (``test_copy``);
* ``bench/traffic/<traffic>.json``: how data is drawn and which
  cluster events run between steps;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

One run builds what ``launch/train.py`` builds (``Model`` ->
``build_profile`` -> ``OobleckEngine`` -> ``HeteroTrainer``), warms the
programs this cell's traffic reaches, takes the first steps that the
correctness check compares, then measures ``trainer.step`` (and, where
the traffic says, ``trainer.recover`` / ``trainer.join``) for a fixed
number of seconds.  After the window it frees the system's state and
trains the configuration's plain reference over the same first steps.
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import statistics
import sys
import time
import typing
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CHECK_STEPS = 3


class BenchError(RuntimeError):
    """The cell cannot be run as specified (no chip, bad spec)."""


# ----------------------------------------------------------------------
# Resolving a cell from BENCHMARK.json
# ----------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict            # bench/configs/<config>.json
    traffic: Dict           # bench/traffic/<traffic>.json
    end_to_end: List[Dict]  # the metrics this cell reports
    per_layer: List[Dict]
    root: Path


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def resolve_cell(name: str, root: Path = ROOT,
                 spec: Optional[Dict] = None) -> Cell:
    spec = spec if spec is not None else load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
                root=root)


def load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: Path = ROOT) -> Callable:
    """``bench/metrics/<metric>.py``'s ``read(ctx)``."""
    return load_module(root / "bench" / "metrics" / f"{metric}.py",
                       f"bench_metric_{metric.replace('.', '_')}").read


def config_module(cfg: Dict, key: str, root: Path = ROOT) -> ModuleType:
    """The module that the configuration names under ``key``
    (``reference``, ``work``), a path relative to the checkout's root."""
    return load_module(root / cfg[key],
                       f"bench_{key}_{cfg['name'].replace('.', '_')}")


# ----------------------------------------------------------------------
# Inputs from the seed
# ----------------------------------------------------------------------
def _rng(seed: int, *stream) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


def batch_rows(seed: int, step: int, rows: int, seq: int, vocab: int):
    """``rows`` sequences of uniform token ids; the labels are the same
    sequences' next tokens.  Every (seed, step) gives other rows."""
    ids = _rng(seed, 1, step).integers(0, vocab, (rows, seq + 1),
                                       dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]


def weight_key(seed: int):
    import jax
    hi, lo = np.random.SeedSequence([seed, 0]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(hi)), int(lo))


class Events:
    """The traffic's cluster events, in order: first ``setup_events``
    (one after each of the first steps), then ``cycle`` over and over,
    one after every ``event_every_steps`` steps of the window.  A join
    adds a fresh node.  A failure kills the node at a position of the
    pipeline layout (pipelines in order, nodes in stage order); the
    positions come in blocks, each block a permutation drawn from the
    seed, so every seed hits every position as often, in its own order."""

    def __init__(self, traffic: Dict, seed: int):
        self.setup = list(traffic.get("setup_events", []))
        self.cycle = list(traffic.get("cycle", []))
        self.every = traffic.get("event_every_steps", 0)
        self.most = traffic.get("max_events", 0)
        self.rng = _rng(seed, 2)
        self.positions: List[int] = []
        self.done = 0

    def window_left(self) -> bool:
        return bool(self.every) and self.done - len(self.setup) < self.most

    def next_kind(self) -> str:
        if self.done < len(self.setup):
            return self.setup[self.done]
        return self.cycle[(self.done - len(self.setup)) % len(self.cycle)]

    def apply(self, engine, trainer=None) -> Dict:
        """Apply the next event through the trainer, or plan it on a bare
        engine when there is no trainer (the result is then empty)."""
        kind = self.next_kind()
        self.done += 1
        if kind == "fail":
            layout = [n for inst in engine.instances for n in inst.nodes]
            if not self.positions:
                self.positions = list(self.rng.permutation(len(layout)))
            arg = {layout[self.positions.pop() % len(layout)]}
            act = trainer.recover if trainer else engine.handle_failure
        else:
            arg = [f"join{self.done}"]
            act = trainer.join if trainer else engine.handle_join
        info = act(arg)
        return {"kind": kind, **(info if trainer else {})}


# ----------------------------------------------------------------------
# The system under test
# ----------------------------------------------------------------------
def init_fn(ref: ModuleType, m: Dict) -> Callable:
    """The weights from a key, made on the device in one jitted call by
    the reference ``ref`` at its sizes ``m``."""
    import jax
    return jax.jit(lambda k: ref.init_params(m, k))


def _dataclass_of(tp) -> Optional[type]:
    """The dataclass that a field's type names, also inside Optional."""
    for t in (tp, *typing.get_args(tp)):
        if dataclasses.is_dataclass(t):
            return t
    return None


def arch_config(cfg: Dict):
    """The registered ``ArchConfig`` of ``cfg["arch"]`` with every field
    that the configuration gives replaced; a nested group becomes the
    dataclass that its field's type names (``ssm``, ``moe`` ...)."""
    from repro.configs import get_arch
    base = get_arch(cfg["arch"])
    types = typing.get_type_hints(type(base))
    sizes = {}
    for f in dataclasses.fields(base):
        if f.name in cfg:
            cls, v = _dataclass_of(types[f.name]), cfg[f.name]
            sizes[f.name] = cls(**v) if cls and isinstance(v, dict) else v
    return dataclasses.replace(base, **sizes)


def model_options(dep: Dict) -> Dict:
    """``Model``'s keyword arguments from the deployment's keys that name
    its fields (dtypes by their ``jax.numpy`` name); the benchmark always
    runs without rematerialisation and with unscanned layers."""
    import jax.numpy as jnp
    from repro.models import Model
    fields = {f.name for f in dataclasses.fields(Model)} - {"arch"}
    opts = {k: getattr(jnp, v) if k.endswith("dtype") else v
            for k, v in dep.items() if k in fields}
    return {**opts, "remat": False, "scan_layers": False}


def build_system(cfg: Dict, params):
    """(model, engine, trainer) as ``launch/train.py`` builds them, and a
    copy of the freshly planned engine for rehearsing events."""
    from repro.core import EngineConfig, OobleckEngine, build_profile
    from repro.models import Model
    from repro.optim import adamw
    from repro.runtime import HeteroTrainer

    arch = arch_config(cfg)
    dep, opt = cfg["deployment"], cfg["optimizer"]
    model = Model(arch, **model_options(dep))
    profile = build_profile(arch, microbatch=dep["microbatch"],
                            seq_len=dep["seq_len"])
    nodes = [f"node{i}" for i in range(dep["nodes"])]
    engine = OobleckEngine(profile, nodes, EngineConfig(
        fault_tolerance=dep["fault_tolerance"],
        global_batch=dep["global_batch"], microbatch=dep["microbatch"],
        gpus_per_node=1, n0_override=dep["n0"],
        recovery_policy=dep["recovery_policy"]))
    opt_cfg = adamw.AdamWConfig(
        lr=opt["lr"], beta1=opt["beta1"], beta2=opt["beta2"],
        eps=opt["eps"], weight_decay=opt["weight_decay"],
        clip_norm=opt["clip_norm"], warmup_steps=0, min_lr_ratio=1.0)
    # the engine is planned before the trainer attaches to it, so a copy
    # taken now can rehearse the traffic's events on the host
    rehearsal = copy.deepcopy(engine)
    trainer = HeteroTrainer(model, engine, params, opt_cfg, mode="compiled")
    return model, engine, trainer, rehearsal


def check_layout(model, tree) -> None:
    """The weights the benchmark makes have the system's own layout."""
    import jax
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: tree)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise BenchError("the benchmark's weights do not have the "
                         "system's parameter layout")


def step_batches(engine, tokens, labels, microbatch: int):
    """Cut one step's rows into each pipeline's microbatches."""
    out, row = [], 0
    for size in engine.batch.minibatch_sizes():
        mbs = [{"tokens": tokens[r:r + microbatch],
                "labels": labels[r:r + microbatch]}
               for r in range(row, row + size, microbatch)]
        out.append(mbs)
        row += size
    return out


def reachable_mb_counts(rehearsal, traffic: Dict, seed: int) -> List[int]:
    """Microbatch counts of every layout this run's events reach (the
    same victims), planned on a host-side copy of the engine."""
    counts = set(rehearsal.batch.num_microbatches)
    events = Events(traffic, seed)
    while events.done < len(events.setup) or events.window_left():
        events.apply(rehearsal)
        counts.update(rehearsal.batch.num_microbatches)
    return sorted(counts)


# ----------------------------------------------------------------------
# Readings of the system's state for the correctness check
# ----------------------------------------------------------------------
def _per_layer(run, field: str, n_layers: int):
    return [run.states[l][field] for l in range(n_layers)]


def state_norms(trainer, field: str, scale: float = 1.0) -> np.ndarray:
    """[replicas, leaves] norms of every replica's ``field`` state."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda ls: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32)))) * scale
        for l in jax.tree.leaves(ls)]))
    return np.stack([np.asarray(fn(_per_layer(r, field, trainer.num_layers)))
                     for r in trainer.runs])


def change_norms(trainer, ref: ModuleType, m: Dict, start) -> np.ndarray:
    """[replicas, leaves] norms of each replica's parameter change since
    ``start`` (the full tree the system was built from, split into
    layers by the reference ``ref``)."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda a, tree: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x - y))) for x, y in zip(
            jax.tree.leaves(a),
            jax.tree.leaves(ref.split_layers(m, tree)))]))
    return np.stack([np.asarray(fn(_per_layer(r, "p", trainer.num_layers),
                                   start))
                     for r in trainer.runs])


def replica_gap(trainer) -> float:
    """Largest |difference| between any two replicas' parameters."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda a, b: jnp.max(jnp.stack([
        jnp.max(jnp.abs(x - y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])))
    base = _per_layer(trainer.runs[0], "p", trainer.num_layers)
    gaps = [float(fn(base, _per_layer(r, "p", trainer.num_layers)))
            for r in trainer.runs[1:]]
    return max(gaps, default=0.0)


def program_leaf_names(trainer) -> List[str]:
    import jax
    run = trainer.runs[0]
    return [f"{l}{jax.tree_util.keystr(path)}"
            for l in range(trainer.num_layers)
            for path, _ in jax.tree_util.tree_flatten_with_path(
                run.states[l]["p"])[0]]


# ----------------------------------------------------------------------
# The comparison that decides `correct`
# ----------------------------------------------------------------------
def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray,
                   keep: Optional[np.ndarray] = None) -> float:
    """Worst leaf, over replicas, of |norm_prog - norm_ref| measured
    against max(norm_ref of that leaf, median leaf's norm_ref)."""
    keep = np.ones(ref.shape, bool) if keep is None else keep
    floor = np.maximum(ref, np.median(ref[keep]))
    gaps = np.abs(prog - ref[None, :]) / floor[None, :]
    return float(np.max(gaps[:, keep]))


def compare(readings: Dict, ref: Dict, limits: Dict) -> Dict:
    """Each number that has a limit, beside its limit."""
    g_ref = ref["grad_norms"]
    # leaves whose reference gradient is nought to rounding move under
    # Adam by round-off alone: their change is not compared
    moved = g_ref >= 1e-3 * np.median(g_ref)
    nums = {
        "loss_gap": float(np.max(np.abs(readings["losses"]
                                        - ref["losses"]))),
        "grad_gap": worst_leaf_gap(readings["grad_norms"], g_ref),
        "change_gap": worst_leaf_gap(readings["change_norms"],
                                     ref["change_norms"], moved),
        "replica_gap": readings["replica_gap"],
    }
    return {k: {"value": v, "limit": limits[k]} for k, v in nums.items()
            if k in limits}


def is_correct(checks: Dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


# ----------------------------------------------------------------------
# One run
# ----------------------------------------------------------------------
class CompileCounter:
    """Programs lowered while ``active`` (jax.monitoring events)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and event == self.EVENT:
            self.count += 1

    def close(self) -> None:
        try:
            from jax._src import monitoring
            monitoring._unregister_event_duration_listener_by_callback(
                self._on)
        except (ImportError, AttributeError, ValueError):
            self.active = False


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, trace_dir: Optional[Path] = None,
             log=lambda msg: print(msg, file=sys.stderr, flush=True)
             ) -> Dict:
    """Set up, warm, measure, check.  Returns the result line's dict.
    With ``trace``, the profiler writes under ``trace_dir`` (removed
    after the reduction)."""
    import jax

    cfg, traffic = cell.config, cell.traffic
    dep = cfg["deployment"]
    reference = config_module(cfg, "reference", cell.root)
    m = reference.sizes(cfg)
    seq, rows, mb = dep["seq_len"], dep["global_batch"], dep["microbatch"]
    dev = jax.devices()[0]

    init = init_fn(reference, m)
    params = init(weight_key(seed))
    model, engine, trainer, rehearsal = build_system(cfg, params)
    check_layout(model, params)
    events = Events(traffic, seed)
    if events.every:
        trainer.warm_templates(reachable_mb_counts(rehearsal, traffic, seed))
    del rehearsal

    # -- the first steps, through the window's own call and feed -------
    losses, grad_norms = [], None
    for step in range(CHECK_STEPS):
        tokens, labels = batch_rows(seed, step, rows, seq, cfg["vocab_size"])
        out = trainer.step(step_batches(engine, tokens, labels, mb))
        jax.block_until_ready((out["loss"], [r.states for r in trainer.runs]))
        losses.append(float(out["loss"]))
        if step == 0:
            # Adam's first moment after one step is (1 - beta1) g
            b1 = cfg["optimizer"]["beta1"]
            grad_norms = state_norms(trainer, "m", 1.0 / (1.0 - b1))
        if step < len(events.setup):
            events.apply(engine, trainer)
    readings = {"losses": np.asarray(losses), "grad_norms": grad_norms,
                "change_norms": change_norms(trainer, reference, m,
                                            params)}
    names = program_leaf_names(trainer)
    del params
    gc.collect()

    # -- the window --------------------------------------------------------
    counter = CompileCounter()
    tokens_per_step = rows * seq
    if trace:
        trace_dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(trace_dir))
    annotate = (jax.profiler.TraceAnnotation if trace
                else lambda name: contextlib.nullcontext())
    setup_s = time.perf_counter() - t_start
    counter.active = True
    steps = attempted = failed = 0
    log_events: List[Dict] = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    step = CHECK_STEPS
    with annotate("bench.window"):
        while time.perf_counter() < deadline:
            attempted += 1
            with annotate("bench.batch"):
                tokens, labels = batch_rows(seed, step, rows, seq,
                                            cfg["vocab_size"])
                batches = step_batches(engine, tokens, labels, mb)
            with annotate("bench.step"):
                out = trainer.step(batches)
            with annotate("bench.loss"):
                jax.block_until_ready((out["loss"],
                                       [r.states for r in trainer.runs]))
            t_done = time.perf_counter()
            steps += 1
            step += 1
            if log_events and "first_step_end" not in log_events[-1]:
                log_events[-1]["first_step_end"] = t_done - t0
            if (events.every and steps % events.every == 0
                    and events.window_left() and t_done < deadline):
                kind = events.next_kind()
                ta = time.perf_counter()
                with annotate("bench.recover" if kind == "fail"
                              else "bench.join"):
                    info = events.apply(engine, trainer)
                    jax.block_until_ready([r.states for r in trainer.runs])
                tb = time.perf_counter()
                log_events.append({
                    "kind": kind, "t_call": ta - t0, "t_bound": tb - t0,
                    "replan_s": info["breakdown"]["replan"],
                    "copied_bytes": info["copied_bytes"]})
    t1 = time.perf_counter()
    counter.active = False
    counter.close()
    if trace:
        jax.profiler.stop_trace()
    window_s = t1 - t0
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    # event times are seconds since the window opened
    window = {"steps": steps, "seconds": window_s,
              "tokens": steps * tokens_per_step, "events": log_events,
              "window_compiles": counter.count}
    log(f"[bench] {cell.name}: {steps} steps, {len(log_events)} events in "
        f"{window_s:.3f}s; setup {setup_s:.3f}s; peak {peak} bytes")

    # -- per-layer readings that need the trainer ---------------------------
    programs = program_calls(trainer) if trace else {}
    readings["replica_gap"] = replica_gap(trainer)
    del trainer, engine, out, model, batches
    gc.collect()

    # -- the plain reference, after the system's state is freed -------------
    t_ref = time.perf_counter()
    params = init(weight_key(seed))
    ref = reference.run_steps(
        m, cfg["optimizer"], params,
        [batch_rows(seed, s, rows, seq, cfg["vocab_size"])
         for s in range(CHECK_STEPS)])
    del params
    ref_names = reference.leaf_names(jax.eval_shape(
        lambda k: reference.split_layers(m, reference.init_params(m, k)),
        jax.random.PRNGKey(0)))
    if ref_names != names:
        raise BenchError("reference and system leaves differ: "
                         f"{sorted(set(ref_names) ^ set(names))[:5]}")
    checks = compare(readings, ref, cfg["limits"])
    log(f"[bench] reference {time.perf_counter() - t_ref:.1f}s")

    metrics = end_to_end(cell, window, setup_s)
    result = {"correct": is_correct(checks), "attempted": attempted,
              "failed": failed, "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": peak}}
    if trace:
        from bench import trace as trace_mod
        t_red = time.perf_counter()
        red = trace_mod.reduce_dir(trace_dir, programs)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = reader_context(cell, window, red, dev.device_kind, peak)
        per_layer = {}
        for metric in cell.per_layer:
            value = load_reader(metric["name"], cell.root)(ctx)
            if value is not None:
                per_layer[metric["name"]] = {"value": value,
                                             "unit": metric["unit"]}
        result["metrics"] = per_layer
        log(f"[bench] trace of {len(red.events)} events reduced in "
            f"{time.perf_counter() - t_red:.1f}s")
        result["device"]["busy_s"] = red.busy_s
        result["device"]["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    result["window"] = window
    result["checks"] = checks
    return result


def reader_context(cell: Cell, window: Dict, red, device_kind: str,
                   memory_peak_bytes: int) -> Dict:
    """What the per-layer readers read (``bench/metrics/_common.py``)."""
    from bench.peaks import peaks
    cfg = cell.config
    work = config_module(cfg, "work", cell.root)
    return {"cell": cell, "config": cfg, "window": window, "trace": red,
            "peaks": peaks(device_kind), "chips": cell.chips,
            "memory_peak_bytes": memory_peak_bytes, "root": cell.root,
            "flops_per_token": work.flops_per_token(cfg)}


def end_to_end(cell: Cell, window: Dict, setup_s: float) -> Dict:
    names = {m["name"]: m["unit"] for m in cell.end_to_end}
    rate = window["tokens"] / window["seconds"]
    fails = [e for e in window["events"] if e["kind"] == "fail"
             and "first_step_end" in e]
    values = {"setup_s": setup_s, "tokens_per_s": rate,
              "goodput_tokens_per_s": rate}
    if fails:
        values["recover_s"] = statistics.fmean(
            e["first_step_end"] - e["t_call"] for e in fails)
    return {n: {"value": values[n], "unit": u} for n, u in names.items()
            if n in values}


def program_calls(trainer) -> Dict[str, List[Dict]]:
    """kind -> [{"module": HLO module name, "calls": {instruction:
    kernel function}}] of every program in the trainer's cache, grouped
    by the cache key's kind (``grads``, ``bpack``, ``bupdate`` ...)."""
    from bench.trace import kernel_calls
    out: Dict[str, List[Dict]] = {}
    for key in trainer.cache.keys():
        kind = (key[1] if trainer.cache.namespace is not None else key)[0]
        text = trainer.cache._programs[key].as_text()
        module = text.split(None, 2)[1].rstrip(",")
        out.setdefault(kind, []).append({"module": module,
                                         "calls": kernel_calls(text)})
    return out
