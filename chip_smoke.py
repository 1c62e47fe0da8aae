"""Run the system's main path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the multi-chip fan-out alone

With one chip, in one process and in this order:

1. control plane at deployment size: the benchmark's own deployment,
   ``bench/configs/ec2-paper-100k.json`` (100,000 streams over 512 hash
   cells), built by ``bench.fleet``.  The batched cold pack
   (``reset(pack="batched")``) is checked against the serial numpy pack of
   every cell, a 192-event ``apply_events`` replay (six blocks of the
   ``bench/traffic/recertify32.json`` mix) against the serial per-event
   loop on a twin controller, and every dispatch of the stacked
   certification (``refresh_prices``) against the numpy pricing
   reference.  All must agree bit for bit.  The certified lower bound
   must also match, within ``LB_REL_TOL``, the one a twin controller
   certifies with the host's float64 pricing DP.
2. the paper's analysis program: VGG-16 on 32 synthetic 640x480 frames,
   against the same forward on the host CPU.  TPU matmuls and convolutions
   at default precision round their float32 inputs to bfloat16, which
   moves VGG-16's output by about 1% of its largest magnitude (measured
   on the CPU by rounding the same inputs), so the check allows
   ``VGG16_REL_TOL`` of it.
3. a served model at full width: internlm2-1.8b with random bf16 weights
   through ``ServingEngine``, 8 requests of 512 prompt tokens and 16 greedy
   new tokens, served cold and then warm.  Every request must be answered
   with finite logits, the warm run must repeat the cold one, and the last
   decode step's logits must match a full prefill of prompt plus generated
   tokens within ``tests/test_serving_consistency.py``'s bf16 tolerance.

``--chips 4`` runs only what exists across chips: the 512-cell batched
pack and one stacked pricing dispatch, each fanned out by ``jax.pmap``
and compared bit for bit with the single-device result.

Each phase prints one JSON line: wall seconds (every result waited for),
the XLA compiles it asked for with their seconds (persistent-cache hits
included and counted apart), and the device's ``peak_bytes_in_use`` so
far.  The last line is ``{"ok": true, "device": {...}}``, printed only
when every check passed.  Without a TPU the script exits non-zero and
prints no result.  Weights, frames and the order of the events inside
each block come from ``--seed``; the fleet and the events are the
benchmark's own.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import json
import pathlib
import sys
import time
import traceback

import jax
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
CONFIG = ROOT / "bench" / "configs" / "ec2-paper-100k.json"
TRAFFIC = ROOT / "bench" / "traffic" / "recertify32.json"
#: Blocks of the traffic's 32-event mix replayed: 192 events.
EVENT_BLOCKS = 6

#: Allowed relative gap between the certified lower bounds of the
#: fixed-point and the float64 pricing DP (the master LP's tolerance).
LB_REL_TOL = 1e-9
VGG16_FRAMES = 32
#: Allowed max |TPU - CPU| over the CPU output's max magnitude (VGG-16).
VGG16_REL_TOL = 0.05
SERVE_ARCH = "internlm2-1.8b"
SERVE_REQUESTS = 8
SERVE_NEW_TOKENS = 16
#: bf16 agreement of decode vs prefill logits, as in the serving tests.
SERVE_TOL = 0.08


class _Compiles:
    """XLA compile requests seen by this process, and their seconds."""

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.count, self.seconds, self.cache_hits


class Smoke:
    """Runs phases, prints one JSON line each, and collects failed checks."""

    def __init__(self) -> None:
        self.device = jax.devices()[0]
        self.compiles = _Compiles()
        self.failures: list[str] = []

    def check(self, rec: dict, name: str, ok: bool) -> None:
        rec.setdefault("checks", {})[name] = bool(ok)
        if not ok:
            self.failures.append(f"{rec['phase']}: {name}")

    @contextlib.contextmanager
    def phase(self, name: str):
        rec: dict = {"phase": name}
        c0, s0, h0 = self.compiles.snapshot()
        t0 = time.perf_counter()
        try:
            yield rec
        except Exception:  # noqa: BLE001 - report the phase, run the rest
            rec["error"] = traceback.format_exc()
            self.failures.append(f"{name}: raised")
        rec["wall_s"] = time.perf_counter() - t0
        c1, s1, h1 = self.compiles.snapshot()
        rec["compiles"] = c1 - c0
        rec["compile_s"] = s1 - s0
        rec["compile_cache_hits"] = h1 - h0
        stats = self.device.memory_stats() or {}
        rec["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        print(json.dumps(rec), flush=True)


@contextlib.contextmanager
def recorded_pricing():
    """Record every ``price_knapsacks`` call: (args, kwargs, result)."""
    from repro.kernels import knapsack

    calls: list = []
    real = knapsack.price_knapsacks

    def spy(*args, **kwargs):
        result = real(*args, **kwargs)
        calls.append((args, kwargs, result))
        return result

    knapsack.price_knapsacks = spy
    try:
        yield calls
    finally:
        knapsack.price_knapsacks = real


@contextlib.contextmanager
def float64_pricing():
    """Price on the host's float64 DP, as the kernel did before fixed point."""
    from repro.kernels import knapsack

    real = knapsack.price_knapsacks
    knapsack.price_knapsacks = (
        lambda v, w, b, c, impl=None: knapsack._price_float64(v, w, b, c)
    )
    try:
        yield
    finally:
        knapsack.price_knapsacks = real


def _cell_bins(ctrl) -> list:
    return [(b.bin_type.name, dict(b.members)) for b in ctrl._bins]


def _reference_bins(problem) -> list:
    """Bins of the serial numpy FFD pack, in the shape of `_cell_bins`."""
    from repro.core.binpack import heuristics

    sol = heuristics.first_fit_decreasing(problem)
    bins = [(b.bin_type.name, {}) for b in sol.bins]
    for a in sol.assignments:
        item = problem.items[a.item_index]
        bins[a.bin_index][1][item.name] = item.choices[a.choice_index].label
    return bins


def control_plane(smoke: Smoke, rec: dict, cfg: dict, traffic: dict, *,
                  seed: int, blocks: int) -> None:
    """The deployment ``cfg`` and ``blocks`` blocks of ``traffic``'s mix."""
    from bench.fleet import build_controller, build_fleet, closed_loop_trace
    from repro.kernels import knapsack

    events = closed_loop_trace(cfg, traffic, build_fleet(cfg), seed, blocks)
    t0 = time.perf_counter()
    batched = build_controller(cfg)
    rec["reset_batched_s"] = time.perf_counter() - t0
    rec["streams"], rec["cells"] = len(batched.fleet), batched.n_cells
    cells = list(batched.cells.values())
    t0 = time.perf_counter()
    bad = sum(_cell_bins(c) != _reference_bins(c._problem) for c in cells)
    rec["reset_numpy_reference_s"] = time.perf_counter() - t0
    rec["reset_mismatched_cells"] = bad
    smoke.check(rec, "cold_pack_matches_numpy", bad == 0)

    serial = build_controller(cfg)
    t0 = time.perf_counter()
    serial_costs, serial_lbs, last = [], [], None
    for ev in events:
        last = serial.apply(ev)
        serial_costs.append(last.plan.hourly_cost)
        serial_lbs.append(last.lower_bound)
    rec["replay_serial_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    rb = batched.apply_events(events)
    rec["replay_batched_s"] = time.perf_counter() - t0
    delta = max(
        max(abs(x - y.plan.hourly_cost) for x, y in zip(serial_costs, rb)),
        max(abs(x - y.lower_bound) for x, y in zip(serial_lbs, rb)),
    )
    horizon = events[-1].at + 1.0
    same_fleet = (
        last.plan.placements == rb[-1].plan.placements
        and last.plan.instances == rb[-1].plan.instances
        and serial.instance_uids == batched.instance_uids
        and serial.lifecycle.billed_cost(horizon)
        == batched.lifecycle.billed_cost(horizon)
    )
    rec["replay_events"] = len(rb)
    rec["replay_delta"] = delta if same_fleet else float("inf")
    smoke.check(rec, "replay_matches_serial", delta == 0.0 and same_fleet)

    float64_twin = copy.deepcopy(batched)
    with recorded_pricing() as calls:
        t0 = time.perf_counter()
        lb = batched.refresh_prices()
        rec["certify_s"] = time.perf_counter() - t0
    rec["lower_bound"], rec["hourly_cost"] = lb, batched.total_cost()
    smoke.check(rec, "lower_bound_admissible",
                0.0 < lb <= batched.total_cost() + 1e-6)
    # The same certification on the float64 DP: fixed point may break
    # pricing ties differently, but converged cells certify their LP
    # optimum either way, so the bounds agree to LP tolerance.
    t0 = time.perf_counter()
    with float64_pricing():
        lb_float64 = float64_twin.refresh_prices()
    rec["certify_float64_reference_s"] = time.perf_counter() - t0
    rec["lower_bound_float64_dp"] = lb_float64
    smoke.check(rec, "lower_bound_matches_float64_dp",
                abs(lb - lb_float64) <= LB_REL_TOL * lb_float64)
    t0 = time.perf_counter()
    mismatched = 0
    for args, kwargs, got in calls:
        ref = knapsack.price_knapsacks(*args, **{**kwargs, "impl": "numpy"})
        mismatched += not (np.array_equal(got.best, ref.best)
                           and np.array_equal(got.counts, ref.counts))
    rec["pricing_numpy_reference_s"] = time.perf_counter() - t0
    rec["pricing_dispatches_mismatched"] = mismatched
    first = calls[0][2]
    rec["pricing_dispatch_shape"] = {
        "knapsacks": len(first.best), "entries": first.counts.shape[1],
        "states": first.states, "steps": first.steps,
    }
    smoke.check(rec, "pricing_matches_numpy", mismatched == 0)

    st = batched.stats()
    rec["counters"] = {
        k: st[k] for k in (
            "batched_repair_dispatches", "serial_repair_dispatches",
            "pricing_dispatches", "pricing_rounds", "events_routed",
        )
    }
    smoke.check(rec, "batched_repair_dispatched",
                st["batched_repair_dispatches"] >= 1)
    smoke.check(rec, "pricing_dispatched", st["pricing_dispatches"] >= 1)


def vgg16(smoke: Smoke, rec: dict, *, seed: int, n_frames: int) -> None:
    from repro.models import analysis_programs as ap

    params = ap.init_vgg16(jax.random.PRNGKey(seed))
    frames = np.random.RandomState(seed).randint(
        0, 256, (n_frames, 480, 640, 3), np.uint8
    )
    forward = jax.jit(jax.vmap(ap.vgg16_forward, in_axes=(None, 0)))
    t0 = time.perf_counter()
    out = forward(params, frames).block_until_ready()
    rec["first_call_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = np.asarray(forward(params, frames).block_until_ready())
    rec["warm_call_s"] = time.perf_counter() - t0
    rec["frames"] = n_frames
    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    ref = np.asarray(
        forward(jax.device_put(params, cpu), jax.device_put(frames, cpu))
    )
    rec["cpu_reference_s"] = time.perf_counter() - t0
    rel = float(np.abs(out - ref).max() / np.abs(ref).max())
    rec["max_abs_err_over_max_abs"] = rel
    rec["rel_tol"] = VGG16_REL_TOL
    smoke.check(rec, "shape", out.shape == ref.shape == (n_frames, 1, 105))
    smoke.check(rec, "finite", bool(np.isfinite(out).all()))
    smoke.check(rec, "matches_cpu", rel <= VGG16_REL_TOL)


def serving(smoke: Smoke, rec: dict, cfg, *, seed: int, n_requests: int,
            prompt_len: int, new_tokens: int) -> None:
    from repro.models import transformer as tfm
    from repro.serving import Request, ServingEngine

    max_seq = prompt_len + new_tokens
    t0 = time.perf_counter()
    params = jax.jit(tfm.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg
    )
    jax.block_until_ready(params)
    rec["init_s"] = time.perf_counter() - t0
    rec["params"] = int(sum(x.size for x in jax.tree.leaves(params)))
    prompts = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (n_requests, prompt_len)
    ).astype(np.int32)
    engine = ServingEngine(cfg, params, batch_slots=n_requests, max_seq=max_seq)

    def serve():
        for i in range(n_requests):
            engine.submit(Request(rid=i, prompt=prompts[i],
                                  max_new_tokens=new_tokens))
        t0 = time.perf_counter()
        results = sorted(engine.run(), key=lambda r: r.rid)
        return results, time.perf_counter() - t0

    cold, rec["serve_cold_s"] = serve()
    warm, rec["serve_warm_s"] = serve()
    rec["requests"], rec["prompt_tokens"] = n_requests, prompt_len
    rec["new_tokens"] = new_tokens
    smoke.check(rec, "all_answered",
                [r.rid for r in cold] == list(range(n_requests))
                and all(len(r.tokens) == new_tokens for r in cold))
    smoke.check(rec, "warm_repeats_cold",
                [r.tokens for r in warm] == [r.tokens for r in cold])
    got = np.stack([r.logits for r in cold])
    smoke.check(rec, "logits_finite", bool(np.isfinite(got).all()))

    full = np.concatenate(
        [prompts, np.asarray([r.tokens for r in cold], np.int32)], axis=1
    )
    prefill_last = jax.jit(lambda p, t: tfm.forward_prefill(
        p, cfg, {"tokens": t}, tfm.init_serve_cache(cfg, n_requests, max_seq)
    )[0][:, -1])
    ref = np.asarray(prefill_last(params, full))
    excess = np.abs(got - ref) - (SERVE_TOL + SERVE_TOL * np.abs(ref))
    rec["decode_vs_prefill_max_abs_diff"] = float(np.abs(got - ref).max())
    rec["decode_vs_prefill_tol"] = SERVE_TOL
    smoke.check(rec, "reference_finite", bool(np.isfinite(ref).all()))
    smoke.check(rec, "decode_matches_prefill", float(excess.max()) <= 0.0)


def fan_out(smoke: Smoke, rec: dict, cfg: dict) -> None:
    """pmap over local devices vs the single-device kernels, bit for bit."""
    from bench.fleet import build_fleet, build_manager
    from repro.core.binpack import colgen, heuristics
    from repro.core.shard import hash_cells
    from repro.core.strategies import ST3
    from repro.kernels import knapsack

    rec["devices"] = jax.local_device_count()
    smoke.check(rec, "several_devices", rec["devices"] > 1)
    key, parts, mgr = hash_cells(cfg["cells"]), {}, build_manager(cfg)
    for s in build_fleet(cfg):
        parts.setdefault(key(s), []).append(s)
    probs = [mgr.formulate(part, ST3) for part in parts.values()]
    ts = [p.tensors() for p in probs]
    reqs, masks, scores, orders = heuristics._pad_fleets(probs, ts)
    rec["cells"] = len(probs)
    with jax.enable_x64(True):
        multi = heuristics._dispatch_pack(
            False, reqs, masks, scores, orders, ts[0].caps, ts[0].costs
        )
        single = heuristics._batched_kernel(False)(
            reqs, masks, scores, orders, ts[0].caps, ts[0].costs
        )
    smoke.check(rec, "pack_pmap_matches_vmap", all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(multi), jax.tree.leaves(single))
    ))

    with recorded_pricing() as calls:
        colgen.batched_dual_prices(probs, colgen.ColumnPool(), max_rounds=1)
    args, _kwargs, _got = calls[0]
    values, weights, bounds, caps = (
        np.asarray(args[0]), *(np.asarray(a, np.int64) for a in args[1:4])
    )
    sv, sw, _entry, _mult, shifts, coord, _final = knapsack._dp_inputs(
        values, weights, bounds, caps
    )
    rec["pricing_knapsacks"], rec["pricing_states"] = len(sv), len(coord)
    take_multi = knapsack._dp_jax(sv, sw, shifts, coord)
    with jax.enable_x64(True):
        take_single = np.asarray(knapsack._jax_kernel()(sv, sw, shifts, coord))
    smoke.check(rec, "pricing_pmap_matches_single",
                np.array_equal(take_multi, take_single))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip fan-out check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found; JAX sees {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.configs import default_tokens_per_frame, get_config
    from repro.launch.compile_cache import use_compile_cache

    cfg = json.loads(CONFIG.read_text())
    cache_dir = use_compile_cache()
    smoke = Smoke()
    print(json.dumps({"phase": "setup", "compile_cache": cache_dir,
                      "device_kind": devices[0].device_kind,
                      "devices": len(devices), "jax": jax.__version__}),
          flush=True)
    if args.chips == 4:
        with smoke.phase("fan_out") as rec:
            fan_out(smoke, rec, cfg)
    else:
        with smoke.phase("control_plane") as rec:
            control_plane(smoke, rec, cfg, json.loads(TRAFFIC.read_text()),
                          seed=args.seed, blocks=EVENT_BLOCKS)
        with smoke.phase("vgg16") as rec:
            vgg16(smoke, rec, seed=args.seed, n_frames=VGG16_FRAMES)
        with smoke.phase("serve_" + SERVE_ARCH) as rec:
            serving(smoke, rec, get_config(SERVE_ARCH), seed=args.seed,
                    n_requests=SERVE_REQUESTS,
                    prompt_len=default_tokens_per_frame(SERVE_ARCH),
                    new_tokens=SERVE_NEW_TOKENS)
    if smoke.failures:
        print(json.dumps({"failed": smoke.failures}), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
