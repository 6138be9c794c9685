#!/usr/bin/env python3
"""Chip benchmark of the DCCO round engine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose JAX sees a TPU. The
cell is an entry of ``BENCHMARK.json``; its files are found by name:
``bench/workloads/<cell>.json`` (engine settings and the limits of the
output check), ``bench/configs/<config>.json`` (the model) and
``bench/traffic/<traffic>.json`` (data, partition, cohort, optimizer).
The configuration names its code: its ``"family"`` is the module
``bench/families/<family>.py`` (the program's model config, the data's
per-sample leaves, the FLOPs of a sample), and its ``"reference"`` the
path of its plain encoder, views and initialisation, which
``bench/protocol.py`` drives through the round. So a configuration of a
new architecture comes as new files: family, reference, config, traffic,
workload, and any per-layer readers under ``bench/metrics/``.

Set-up makes the data and the parameters from the seed, builds the round
engine as the trainer does (``repro.launch.train.main``), and drives it
through its first segments, the ones the output check follows. The
window then calls ``RoundEngine.run`` for whole segments until
``--seconds`` have passed, waiting for each segment's losses. Once the
window has closed and the program's state is freed, the plain reference
follows the first rounds again and decides ``correct``. The last line of
standard output is one JSON object; with ``--trace 1`` it carries the
cell's per-layer metrics, read from a profiler trace of the window by the
files under ``bench/metrics/``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if __name__ == "__main__":
    # the script's own directory would shadow standard modules (trace)
    sys.path[:] = [p for p in sys.path
                   if os.path.abspath(p or ".") != BENCH_DIR]
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import traffic as traffic_lib  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_traces")
CHECK_STEPS = 3                      # rounds whose losses are printed
# the numbers ``compare`` gives; a cell's limits name those it compares
CHECK_NUMBERS = ("loss_gap", "loss_gap_max", "change_gap", "grad_gap",
                 "grad_rms_gap", "change_gap_median", "grad_gap_median",
                 "grad_rms_gap_median")


class NoChip(RuntimeError):
    pass


# -- the cell, by name ----------------------------------------------------------

def _read(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    bench = _read(root, "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}[name]
    cell = _read(root, "bench", "workloads", name + ".json")
    model = _read(root, "bench", "configs", entry["config"] + ".json")
    traffic = _read(root, "bench", "traffic", entry["traffic"] + ".json")

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {"name": name, "root": root, "entry": entry, "cell": cell,
            "model": model, "traffic": traffic,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def load_module(root: str, path: str):
    """The Python file at ``path`` (relative to ``root``), imported once
    per process."""
    path = os.path.abspath(os.path.join(root, path))
    name = "bench_file_" + "".join(c if c.isalnum() else "_" for c in path)
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


def family(spec: dict):
    """The configuration's family module, ``bench/families/<family>.py``."""
    return load_module(spec["root"], os.path.join(
        "bench", "families", spec["model"]["family"] + ".py"))


def reference(spec: dict):
    """The configuration's plain encoder, views and initialisation."""
    return load_module(spec["root"], spec["model"]["reference"])


def read_peaks(device_kind: str) -> dict:
    peaks = _read(BENCH_DIR, "peaks.json")
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json; add its peaks with their source")
    return peaks[device_kind]


# -- set-up clock -------------------------------------------------------------------

class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling in this process
    (its ``/jax/core/compile/*`` monitoring events), and how many
    programs it compiled. Copied from ``chip_smoke.py``, with counts."""

    def __init__(self, jax):
        self.seconds = {"trace": 0.0, "lower": 0.0, "compile": 0.0}
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if not name.startswith("/jax/core/compile/"):
            return
        if name.endswith("jaxpr_trace_duration"):
            self.seconds["trace"] += secs
        elif name.endswith("jaxpr_to_mlir_module_duration"):
            self.seconds["lower"] += secs
        elif name.endswith("backend_compile_duration"):
            self.seconds["compile"] += secs
            self.compiles += 1


# -- building the system under test, as the trainer does ---------------------------

def build_engine(spec: dict, data, compute_dtype=None, server_hook=None):
    """The round engine and its server optimizer, built the way
    ``repro.launch.train.main`` builds them in engine mode;
    ``server_hook`` wraps the server update (a planted fault)."""
    from repro import comm, objectives
    from repro.core import round_engine
    from repro.data.pipeline import FederatedDataset
    from repro.launch import train
    from repro.optim import optimizers as opt_lib, schedules
    from repro.server import update as server_update_lib

    model, tr, cell = spec["model"], spec["traffic"], spec["cell"]
    cfg, de_cfg = family(spec).model_config(model)
    srv = tr["server"]
    opt = server_update_lib.get_server_update(
        "fedavg_sgd", base_opt=opt_lib.adam(
            schedules.cosine_decay(srv["lr"], srv["cosine_rounds"]),
            b1=srv["b1"], b2=srv["b2"], eps=srv["eps"]))
    if server_hook is not None:
        opt = server_hook(opt)
    channel = comm.get_channel(tr["channel"],
                               quant_kernel=cell.get("quant_kernel", "off"))
    ecfg = round_engine.EngineConfig(
        algorithm="dcco",
        objective=objectives.get_objective("dcco", lam=model["lambda_cco"]),
        lam=model["lambda_cco"], client_lr=tr["client_lr"],
        local_steps=tr["local_steps"], chunk_rounds=cell["chunk_rounds"],
        cohort_chunk=cell["cohort_chunk"], stats_kernel=cell["stats_kernel"],
        channel=channel,
        compute_dtype=compute_dtype or model["compute_dtype"],
        server_update=opt)
    ds = FederatedDataset({k: v.reshape((-1,) + data.shapes[k])
                           for k, v in data.leaves.items()},
                          data.labels, data.client_index,
                          client_sizes=data.client_sizes)
    ds.staged()
    k = tr["clients_per_round"]
    sampler = (ds.make_streaming_sampler(k, cell["cohort_chunk"])
               if cell["cohort_chunk"] else ds.make_round_sampler(k))
    return train.make_apply(cfg, de_cfg), opt, ecfg, ds, sampler


def make_inputs(spec: dict, seed: int):
    """The cell's data and partition from the seed (host arrays)."""
    times = {}
    t = time.perf_counter()
    leaves, shapes, labels = family(spec).make_inputs(spec["traffic"],
                                                      spec["model"], seed)
    times["data"] = time.perf_counter() - t
    t = time.perf_counter()
    index, sizes = traffic_lib.make_partition(spec["traffic"], labels, seed)
    times["partition"] = time.perf_counter() - t
    data = traffic_lib.Traffic(leaves, shapes, labels, index, sizes)
    return data, times


def rounds_key(spec: dict, seed: int) -> np.ndarray:
    """The key of the rounds' cohorts and views: from the seed, or from
    the traffic's ``rounds_seed`` where every seed is to draw the same
    cohorts (and so train the same number of valid samples)."""
    return traffic_lib.jax_key_data(
        spec["traffic"].get("rounds_seed", seed), "rounds")


def make_params(jax, spec: dict, seed: int):
    init_params, model = reference(spec).init_params, spec["model"]
    key = traffic_lib.jax_key_data(seed, "params")
    init = jax.jit(lambda k: init_params(k, model))
    return init(key)


# -- the output check ------------------------------------------------------------

def leaf_gaps(prog_norms, ref_norms):
    """Per leaf: |norm(program) - norm(reference)| over the larger of that
    leaf's reference norm and the median leaf's."""
    med = statistics.median(ref_norms)
    return [abs(a - b) / max(b, med, 1e-30)
            for a, b in zip(prog_norms, ref_norms)]


def kept_elements(first_grad, rel=1e-3):
    """Per leaf, a mask of the elements whose reference gradient is not
    nought to rounding: at least ``rel`` of the median leaf's root mean
    square element. The rest (most of the head's first bias, where the
    mean-centred correlation cancels a unit's samples against each other;
    the last layer's bias whole) move under Adam's first, sign-like steps
    by round-off alone, and are left out by this rule on the reference."""
    leaves = [np.abs(np.asarray(g, np.float32))
              for g in jax_leaves(first_grad)]
    rms = statistics.median(float(np.sqrt(np.mean(np.square(g))))
                            for g in leaves)
    return [g >= rel * rms for g in leaves]


def kept_norms(tree, keep, fn=None):
    """Float64 norm of each leaf over its kept elements (``fn`` applied
    to the elements first)."""
    out = []
    for x, k in zip(jax_leaves(tree), keep):
        x = np.asarray(x, np.float64)[k]
        out.append(float(np.sqrt(np.sum(fn(x) if fn else np.square(x)))))
    return out


def jax_leaves(tree):
    import jax
    return jax.tree.leaves(tree)


def leaf_names(tree):
    """A short path of every leaf, in ``jax.tree.leaves`` order."""
    import jax
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def compare(spec, prog, ref) -> dict:
    """The numbers of the check (PERF.md, "How correct is decided"), from
    the window's first segment and the reference's rounds over it:

      loss_gap      the first round's loss against the reference's;
      loss_gap_max  the worst round's, over the segment;
      change_gap    the worst leaf's gap of the parameters' change;
      grad_gap      the same of Adam's first moment, the gradient as the
                    optimizer got it over the segment;
      grad_rms_gap  the same of the root of Adam's second moment, the
                    gradient's size over the segment's rounds;
      *_gap_median  the median leaf's, in place of the worst.

    Norms are taken over the elements ``kept_elements`` keeps. Returns
    (numbers, observed): a cell compares the numbers its limits name; the
    rest are printed with the worst leaves' names."""
    lp, lr = prog["losses"], ref["losses"]
    gaps = [abs(a - b) / max(abs(b), 1e-30) if math.isfinite(a) else math.inf
            for a, b in zip(lp, lr)]
    keep = kept_elements(ref["first_grad"])
    names = leaf_names(ref["first_grad"])
    # the root of Adam's second moment, leaf by leaf: the root of the sum
    # of its kept elements, the gradient's size over the segment's rounds
    per_leaf = {
        "change": leaf_gaps(kept_norms(prog["change"], keep),
                            kept_norms(ref["change"], keep)),
        "grad": leaf_gaps(kept_norms(prog["m"], keep),
                          kept_norms(ref["m"], keep)),
        "grad_rms": leaf_gaps(kept_norms(prog["v"], keep, np.abs),
                              kept_norms(ref["v"], keep, np.abs))}
    numbers = {"loss_gap": gaps[0], "loss_gap_max": max(gaps)}
    observed = {"loss_gap_rounds": gaps,
                "elements_left_out": int(sum(np.sum(~k) for k in keep)),
                "leaves_left_out": sum(not k.any() for k in keep)}
    for what, xs in per_leaf.items():
        xs = [x if math.isfinite(x) else math.inf for x in xs]
        numbers[what + "_gap"] = max(xs)
        numbers[what + "_gap_median"] = statistics.median(xs)
        observed[what + "_worst_leaf"] = names[xs.index(max(xs))]
    return numbers, observed


def run_reference(jax, spec, data, seed, rounds, operand_dtype=None):
    from bench import protocol
    p0 = make_params(jax, spec, seed)
    rng = rounds_key(spec, seed)
    ref = protocol.Reference(reference(spec), spec["model"], data,
                             spec["traffic"], operand_dtype=operand_dtype)
    p, opt, losses, g1 = ref.follow(p0, rng, rounds)
    change = jax.tree.map(lambda a, b: a - b, p, p0)
    return {"losses": losses, "m": opt["m"], "v": opt["v"], "change": change,
            "first_grad": g1}


# -- one run ------------------------------------------------------------------------

def device_info(jax, devices):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, window: bool = True,
             compute_dtype=None, sampler_hook=None, engine_hook=None,
             server_hook=None, log=print) -> dict:
    """One run of a cell. Returns the result object (without printing).

    ``compute_dtype`` overrides the configuration's encoder dtype, and
    ``sampler_hook`` / ``engine_hook`` / ``server_hook`` plant faults
    (``bench/faults.py``).
    With ``window=False`` only the set-up and the check run."""
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    chips = spec["entry"]["chips"]
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"cell {spec['name']} needs {chips} TPU chip(s); JAX "
                     f"sees {len(devices)} {devices[0].platform} device(s)")
    devices = devices[:chips]
    if require_tpu:
        # the checkout's fixed cache directory; every program is kept, so
        # that a second run of the cell compiles nothing
        from repro.utils import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = CompileClock(jax)
    cell, tr, model = spec["cell"], spec["traffic"], spec["model"]
    chunk = cell["chunk_rounds"]

    phases = {}
    data, t = make_inputs(spec, seed)
    phases.update(t)
    t = time.perf_counter()
    apply, opt, ecfg, ds, sampler = build_engine(spec, data, compute_dtype,
                                                 server_hook)
    jax.block_until_ready(ds.staged())
    phases["staging"] = time.perf_counter() - t
    if sampler_hook is not None:
        sampler = sampler_hook(sampler)
    from repro.core import round_engine
    engine = round_engine.RoundEngine(apply, opt, sampler, ecfg)
    if engine_hook is not None:
        engine = engine_hook(engine)
    t = time.perf_counter()
    params = make_params(jax, spec, seed)
    opt_state = jax.jit(opt.init)(params)
    jax.block_until_ready(opt_state)
    phases["params"] = time.perf_counter() - t
    rng = jnp.asarray(rounds_key(spec, seed))

    # the first segment: compiled here, and followed by the reference
    c0 = dict(clock.seconds)
    t = time.perf_counter()
    params, opt_state, m = engine.run(params, opt_state, rng, chunk)
    losses = [float(x) for x in np.asarray(m.loss)]
    p_init = make_params(jax, spec, seed)
    prog = {"losses": losses,
            "m": jax.device_get(opt_state["m"]),
            "v": jax.device_get(opt_state["v"]),
            "change": jax.device_get(jax.tree.map(lambda a, b: a - b,
                                                  params, p_init))}
    del p_init
    first = time.perf_counter() - t
    for k in c0:
        phases[k] = clock.seconds[k] - c0[k]
    phases["first_segment"] = first - sum(phases[k] for k in c0)
    r = check_rounds = chunk

    result = {"attempted": 0, "failed": 0}
    metrics = {}
    if window:
        trace_dir = os.path.join(TRACE_DIR, spec["name"])
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        compiles0 = clock.compiles
        setup_s = time.perf_counter() - T_START
        t0 = time.perf_counter()
        first_round, failed, ends = r, 0, []
        while True:
            with jax.profiler.TraceAnnotation("bench.segment"):
                params, opt_state, m = engine.run(params, opt_state, rng,
                                                  chunk, start_round=r)
            with jax.profiler.TraceAnnotation("bench.pull"):
                seg_losses = np.asarray(m.loss)
            failed += int(np.sum(~np.isfinite(seg_losses)))
            r += chunk
            ends.append(time.perf_counter() - t0)
            if ends[-1] >= seconds:
                break
        elapsed = ends[-1]
        window_compiles = clock.compiles - compiles0
        if trace:
            jax.profiler.stop_trace()
        rounds = r - first_round
        finite = bool(jax.jit(lambda p: jnp.all(jnp.stack(
            [jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(p)])))(params))
        if not finite:
            failed = max(failed, chunk)
        samples = valid_samples(jax, jnp, data, tr, rng, first_round, rounds)
        counter = memory_peak(devices)
        compiled = compiled_segment(jax, jnp, engine, params, opt_state, rng)
        ma = compiled.memory_analysis()
        plan = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        log(f"window: {rounds} rounds in {rounds // chunk} segments, "
            f"{elapsed:.4f} s, {samples} valid samples, "
            f"compiles inside the window: {window_compiles}")
        log("segment seconds: " + " ".join(
            f"{b - a:.4f}" for a, b in zip([0.0] + ends, ends)))
        log(f"memory: compiled segment arguments "
            f"{ma.argument_size_in_bytes} outputs {ma.output_size_in_bytes} "
            f"temporaries {ma.temp_size_in_bytes} aliased "
            f"{ma.alias_size_in_bytes}: {plan}; peak_bytes_in_use "
            f"{counter} of "
            f"{(devices[0].memory_stats() or {}).get('bytes_limit', 0)}")
        peak = max(plan, counter)
        result.update(attempted=rounds, failed=failed)
        info = device_info(jax, devices)
        info["memory_peak_bytes"] = peak
        peaks = read_peaks(info["kind"]) if require_tpu else None
        sps = samples / elapsed
        per_sample = family(spec).flops_per_sample(model, tr["local_steps"])
        e2e = {"samples_per_s": (sps, "samples/s"),
               "mfu": (100.0 * sps * per_sample /
                       (chips * peaks["bf16_flops_per_s"]) if peaks else None,
                       "%"),
               "hbm_peak_gib": (plan / 2**30, "GiB"),
               "setup_s": (setup_s, "s")}
        if trace:
            from bench import trace as trace_lib
            ctx = trace_context(spec, trace_dir, rounds, samples, per_sample,
                                peaks, chips)
            ctx["conv_ops"] = trace_lib.conv_ops(compiled.as_text())
            red = ctx["reduction"]
            info["busy_s"] = red.busy_s
            info["window_s"] = red.window_s
            for m_spec in spec["per_layer"]:
                reader = importlib.import_module("bench.metrics."
                                                 + m_spec["name"])
                v = reader.read(ctx)
                if v is not None:
                    metrics[m_spec["name"]] = {"value": v,
                                               "unit": m_spec["unit"]}
            result["breakdown"] = red.breakdown()
            shutil.rmtree(trace_dir, ignore_errors=True)
        else:
            for m_spec in spec["end_to_end"]:
                v, unit = e2e[m_spec["name"]]
                if v is not None:
                    metrics[m_spec["name"]] = {"value": v, "unit": unit}
        del compiled
        result["device"] = info
        log("setup: " + " ".join(f"{k}={v:.4f}s" for k, v in phases.items())
            + f" total={setup_s:.4f}s")
    else:
        result["device"] = device_info(jax, devices)

    # the program's state goes before the reference runs
    del engine, params, opt_state, m, sampler, ds, apply, opt
    gc.collect()
    ref = run_reference(jax, spec, data, seed, check_rounds)
    got, observed = compare(spec, prog, ref)
    result["observed"] = observed
    limits = cell["limits"]
    checks = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    result["observed"].update({k: v for k, v in got.items()
                               if k not in limits})
    result["correct"] = (all(v["value"] <= v["limit"]
                             for v in checks.values())
                         and result["failed"] == 0)
    result["metrics"] = metrics
    result["checks"] = checks
    result["phases"] = phases
    result["losses"] = {"program": prog["losses"][:CHECK_STEPS],
                        "reference": ref["losses"][:CHECK_STEPS]}
    return result


def compiled_segment(jax, jnp, engine, params, opt_state, rng):
    """The window's segment program, as compiled (from the cache): its
    HLO names the convolutions the trace reduction looks for."""
    from repro.core import round_engine
    engine = getattr(engine, "engine", engine)
    carry = round_engine.EngineCarry(params, opt_state, rng)
    return engine._segment.lower(carry, jnp.asarray(0, jnp.int32),
                                 engine.sampler).compile()


CONTROL_DTYPE = "float8_e4m3fn"


def run_control(spec: dict, seed: int, *, require_tpu: bool = True) -> dict:
    """The control of the output check: the reference put in the
    program's place with every product's operands in float8 (e4m3, one
    scale per tensor), the precision below the configuration's bfloat16
    products, compared as the program is."""
    import jax
    import jax.numpy as jnp
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"the control of {spec['name']} runs on a TPU")
    data, phases = make_inputs(spec, seed)
    rounds = spec["cell"]["chunk_rounds"]
    ctrl = run_reference(jax, spec, data, seed, rounds,
                         operand_dtype=jnp.dtype(CONTROL_DTYPE))
    ctrl = {k: jax.device_get(v) for k, v in ctrl.items()}
    ref = run_reference(jax, spec, data, seed, rounds)
    got, observed = compare(spec, ctrl, ref)
    limits = spec["cell"]["limits"]
    checks = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    observed.update({k: v for k, v in got.items() if k not in limits})
    return {"correct": all(v["value"] <= v["limit"] for v in checks.values()),
            "checks": checks, "observed": observed, "phases": phases,
            "device": device_info(jax, devices),
            "losses": {"program": ctrl["losses"][:CHECK_STEPS],
                       "reference": ref["losses"][:CHECK_STEPS]}}


def valid_samples(jax, jnp, data, tr, rng, first_round, rounds) -> int:
    """Valid client samples trained in rounds [first_round, +rounds): the
    sizes of each round's cohort, drawn as the engine draws it."""
    from bench import protocol
    sizes = jnp.asarray(data.client_sizes)

    def one(r):
        k_sel, _ = protocol.round_keys(rng, r)
        return jnp.sum(sizes[protocol.cohort(k_sel, data.num_clients,
                                             tr["clients_per_round"])])

    rs = jnp.arange(first_round, first_round + rounds)
    return int(jax.jit(jax.vmap(one))(rs).sum())


def trace_context(spec, trace_dir, rounds, samples, per_sample, peaks,
                  chips) -> dict:
    """What the per-layer readers in ``bench/metrics/`` get."""
    from bench import trace as trace_lib
    red = trace_lib.Reduction(trace_lib.load(trace_lib.find_xplane(trace_dir)))
    return {"reduction": red, "rounds": rounds, "samples": samples,
            "model_flops": samples * per_sample, "peaks": peaks,
            "chips": chips, "spec": spec}


def output(result: dict) -> dict:
    """The result line: the contract's keys, the checks last."""
    out = {k: result[k] for k in ("correct", "attempted", "failed",
                                  "metrics", "device")}
    if "breakdown" in result:
        out["breakdown"] = result["breakdown"]
    out["checks"] = result["checks"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    try:
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(f"losses: {json.dumps(result['losses'])}")
    print(f"observed: {json.dumps(result['observed'])}")
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(output(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
