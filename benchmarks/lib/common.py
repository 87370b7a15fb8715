"""What both drivers share: the files of a cell, the device's name and
peaks, build events, the readers of per-layer metrics, the result line."""
import importlib.util
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(bench, name):
    """The cell's entry, its configuration (model sizes as a flat dict) and
    its traffic mix, each from the file that BENCHMARK.json names."""
    from . import traffic

    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    return cell, config, traffic.load(cell["traffic"])


def load_family(config, directory=os.path.join(BENCH_DIR, "families")):
    """families/<family>.py, named by the configuration's ``program.family``:
    the one place an architecture lives (its leaves, the program's model,
    its plain reference, its counts). There is no default."""
    name = config.get("program", {}).get("family")
    path = os.path.join(directory, f"{name}.py")
    if not name or not os.path.isfile(path):
        raise SystemExit(f"benchmark: the configuration's program.family is "
                         f"{name!r}: it has to name a file <family>.py in "
                         f"{directory}")
    return _load_file("bench_family_" + name, path)


def load_limits(cell_name):
    """limits/<cell>.json: {number: limit}. A cell with no file has no
    proven comparison and cannot report correct."""
    path = os.path.join(BENCH_DIR, "limits", cell_name + ".json")
    with open(path) as f:
        return json.load(f)


def metrics_for(bench, cell_name, kind):
    """Entries of ``kind`` ("end_to_end" | "per_layer") that this cell
    reports: those without a workloads key, or that list the cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def read_metric(name, ctx):
    """metrics/<name>.py holds ``read(ctx)``; None: nothing to read."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    return _load_file("bench_metric_" + name, path).read(ctx)


def _load_file(name, path):
    """The module in the file at ``path``, under ``name``."""
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_info(chips):
    """JAX's first devices, or exit: no accelerator, too few chips, or a
    device with no published peaks."""
    import jax

    from .peaks import peaks_for

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU; JAX's first device is "
                         f"platform={d.platform!r} kind={d.device_kind!r}")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, "
                         f"JAX has {len(devs)}")
    return ({"platform": d.platform, "kind": d.device_kind,
             "count": len(devs)}, peaks_for(d.device_kind))


def build_model(family, config, seed):
    """The program's model as the family builds it, holding the seed's
    weights. Returns (model, {leaf path: parameter}). Program and reference
    must have the same leaves with the same shapes."""
    from . import weights as W

    model = family.build(config)
    leaves = family.leaves(config)
    tree = W.make_all(W.spec(leaves, family.LEAF_NAMES,
                             config["initializer_range"]), seed)
    named = dict(model.named_parameters())
    params = {}
    for path in leaves:
        pname = family.program_name(path)
        p = named.pop(pname)
        leaf = W.get_leaf(tree, path)
        if tuple(p.shape) != tuple(leaf.shape):
            raise SystemExit(f"benchmark: {pname} is {p.shape}, "
                             f"the file's sizes give {leaf.shape}")
        p._data = leaf
        params[path] = p
    if named:
        raise SystemExit(f"benchmark: the program has leaves the reference "
                         f"does not: {sorted(named)}")
    return model, params


def memory_peak_bytes():
    """Peak on the fullest chip, as the backend reports it."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks)) if peaks else 0


class BuildWatch:
    """XLA program builds seen through jax.monitoring, each with the host
    clock (time.time_ns at its end, and its duration)."""

    def __init__(self):
        import jax

        self.builds = []                      # (end_ns, dur_ns)
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.builds.append((time.time_ns(), int(secs * 1e9)))

    def count_between(self, lo_ns, hi_ns):
        return sum(1 for e, _ in self.builds if lo_ns < e <= hi_ns)

    def spans(self):
        return [(e - d, e) for e, d in self.builds]


def start_trace(trace_dir):
    """Start the profiler and mark the host's clock inside the trace:
    ``bench.sync`` begins at the time.time_ns() that is returned."""
    import jax
    from jax.profiler import TraceAnnotation

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # annotations only: a small trace
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t = time.time_ns()
    with TraceAnnotation("bench.sync"):
        time.sleep(0.001)
    return t


def reduce_trace(trace_dir, sync_ns, window_ns, build_spans):
    """The trace of a run, cut to its window. Host times (the window's
    ends, the builds) are moved onto the trace's clock by ``bench.sync``."""
    from . import trace as T

    events = T.flatten_xplane(trace_dir)
    sync = [e for e in events if e["name"] == T.HOST_PREFIX + "sync"]
    off = sync[0]["start_ns"] - sync_ns if sync else 0
    return T.Reduced(
        events, window=(window_ns[0] + off, window_ns[1] + off),
        host_spans={"compiling": [(a + off, b + off)
                                  for a, b in build_spans]}), events


def quantile(values, q):
    """Linear-interpolated quantile of a non-empty list."""
    v = sorted(values)
    if len(v) == 1:
        return v[0]
    k = (len(v) - 1) * q
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def note(**kw):
    """An earlier line of standard output: notes, never the result."""
    print(json.dumps(kw), flush=True)


def finish(bench, metrics, device, compared, correct, attempted,
           failed, breakdown=None):
    """Each number compared beside its limit as the last lines of standard
    error; then the one result line as the last line of standard output."""
    for k, v in compared.items():
        print(f"compared {k}: value={v['value']} limit={v['limit']} "
              f"ok={v['ok']}", file=sys.stderr, flush=True)
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in metrics.items() if v is not None},
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    print(json.dumps(line), flush=True)
