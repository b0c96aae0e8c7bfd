"""One run of one cell: build, warm, pre-fill, measure, free, compare.

The system under test is built by ``tree_attention_tpu.cli.build_serve_engine``
(the one construction every serve front end shares) and fed through the
``RequestSource`` seam of ``SlotServer.serve``. Everything that measures lives
under the benchmark's own directories.

Nothing here names a model. The configuration file names its family, and
what depends on the architecture is reached through the family's two files,
found by that name like a generator or a metric's reader
(``benchmark/spec.py``): ``adapters/<family>.py`` builds the engine from the
serving flags made here, and ``references/<family>.py`` is the plain
reference ``judge`` compares with. The one model key read here is the
vocabulary's size, the range the traffic draws its ids from.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import time
from typing import Any, Callable, Dict, List, Optional

from benchmark import check, grid, reduce, trace_reduce
from benchmark.spec import Cell, SpecError

TRACE_SECONDS = 3.0      # the traced part of a --trace 1 window, at its end


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader may read."""

    cell: Cell
    seed: int
    slots: int
    recs: List[Any]
    t_open: float
    t_end: float
    report: Dict[str, Any]            # ServeReport.as_dict()
    leak: Dict[str, int]
    flight: Optional[List[Dict[str, Any]]]   # tick records, t_s made absolute
    trace: Optional[Dict[str, Any]]   # Recording.reduce(), or None
    compile_stamps: List[float]
    memory_peak_bytes: int
    device_kind: str
    peaks: Dict[str, Any]


# -- the device ------------------------------------------------------------


def device_info(chips: int, require_tpu: bool) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if require_tpu and platform != "tpu":
        raise NoChip(f"JAX found platform {platform!r}, not a TPU")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX found {len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": chips if require_tpu else len(devs)}


def memory_peak(chips: int) -> int:
    import jax

    peak = 0
    for d in jax.local_devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def load_peaks(cell: Cell, device_kind: str) -> Dict[str, Any]:
    table = cell.spec.load_json("peaks.json")
    if device_kind not in table:
        raise SpecError(
            f"no published peaks for device kind {device_kind!r} in "
            f"peaks.json (known: {sorted(table)}): add a row with its source")
    return table[device_kind]


# -- the engine ------------------------------------------------------------


def serve_flags(config: Dict[str, Any], traffic: Dict[str, Any], seed: int,
                device: str) -> List[str]:
    """The serving half of the engine's flags; the family's adapter adds the
    model's."""
    s = config["serving"]
    max_new = max(grid.values(traffic["outputs"]))
    flags = [
        "--mode", "serve", "--device", device,
        "--dtype", str(config["torch_dtype"]),
        "--slots", str(s["slots"]),
        "--prompt-len", str(int(s["cache_len"]) - max_new),
        "--prompt-jitter", "0", "--max-new-tokens", str(max_new),
        "--prefill-chunk", str(s["prefill_chunk"]),
        "--kv-layout", str(s["kv_layout"]),
        "--admission", str(s["admission"]),
        "--prefix-block", str(s["kv_block"]),
        "--temperature", "0",
        "--seed", str(model_seed(seed)),
    ]
    if s.get("prefix_cache"):
        flags.append("--prefix-cache")
    if s.get("kv_quant"):
        flags += ["--kv-quant", str(s["kv_quant"])]
    return flags


def model_seed(seed: int) -> int:
    """Any whole number the driver gives, folded into 31 bits."""
    return int(seed) % (2 ** 31 - 1)


def build(cell: Cell, seed: int, device: str):
    """The cell's engine, through its family's adapter: ``(setup, server)``."""
    return cell.adapter().build(
        cell.config, serve_flags(cell.config, cell.traffic, seed, device),
        model_seed(seed), device, cell.reference())


def warm_prompts(traffic: Dict[str, Any], chunk: int,
                 multiple: int) -> List[int]:
    """One prompt length per tick shape the traffic can cause: a whole chunk
    and every remainder a prompt of the grid, or one lengthened mid-life,
    can leave. The engine picks the bucket; this only sees that each is
    used once before the window."""
    prompts = grid.values(traffic["prompts"])
    rems = set()
    for p in prompts:
        for j in range(chunk):
            rems.add((p + j * multiple) % chunk)
            if (j + 1) * multiple % chunk == 0:
                break
    out = sorted(r for r in rems if r)
    if 0 in rems or any(p >= chunk for p in prompts):
        out.append(chunk)
    return out


def warm(server, traffic: Dict[str, Any], config: Dict[str, Any],
         seed: int) -> None:
    """Use every tick program once, each alone so that it is the tick's
    shape: a prompt of that many tokens, then one decode tick."""
    from tree_attention_tpu.serving.engine import Request

    s = config["serving"]
    for i, n in enumerate(warm_prompts(traffic, int(s["prefill_chunk"]),
                                       int(traffic["midlife_multiple"]))):
        ids = grid.token_ids(seed, 10 ** 9 + i, n, config["vocab_size"])
        server.serve([Request(uid=10 ** 9 + i, prompt=ids.tolist(),
                              max_new_tokens=2, temperature=0.0)])


# -- one run ---------------------------------------------------------------


class CompileWatch:
    """Stamps every program JAX builds or fetches from its cache."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.stamps: List[float] = []
        self.compiled = self.fetched = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event in self.EVENTS:
            self.stamps.append(time.monotonic())
            self.compiled += event == self.EVENTS[0]
            self.fetched += event == self.EVENTS[1]


def measure(cell: Cell, seed: int, seconds: float, trace: bool, *,
            t_start: float, require_tpu: bool, say, trace_dir: Optional[str]):
    """Build the engine, use every tick shape once, pre-fill the requests
    caught mid-life, run the window, and free the engine. Returns the
    :class:`Run`, the device dict, and ``setup_s``."""
    from benchmark.driver import WindowSource
    from tree_attention_tpu import cli
    from tree_attention_tpu.obs.flight import FLIGHT

    config, traffic = cell.config, cell.traffic
    cli.configure_compile_cache()
    device = device_info(cell.chips, require_tpu)
    peaks = load_peaks(cell, device["kind"]) if require_tpu else {}
    watch = CompileWatch()
    slots = int(config["serving"]["slots"])

    t_build = time.monotonic()
    setup, server = build(cell, seed, "tpu" if require_tpu else "cpu")
    t_warm = time.monotonic()
    warm(server, traffic, config, seed)
    gen = cell.spec.load_module(
        "generators", traffic["kind"] + ".py").Generator(traffic, slots)
    midlife = traffic["midlife"]
    midlife = slots if midlife == "slots" else int(midlife)

    recording = trace_reduce.Recording(trace_dir) \
        if trace and trace_dir else None
    trace_len = min(TRACE_SECONDS, seconds / 2)

    def on_tick(now: float) -> None:
        # Called from the engine's thread at every poll inside the window:
        # the last ``trace_len`` seconds of a --trace 1 window are traced.
        if recording is not None and recording.begin_monotonic is None \
                and now >= source.t_end - trace_len:
            recording.start()

    source = WindowSource(
        server=server, generator=gen, traffic=traffic, seed=seed,
        vocab=int(config["vocab_size"]), seconds=seconds, midlife=midlife,
        midlife_multiple=int(traffic["midlife_multiple"]),
        on_tick=on_tick)
    if trace:
        FLIGHT.clear()
        FLIGHT.arm(capacity=1 << 16)
    t_serve = time.monotonic()
    try:
        report = server.serve(source)
    finally:
        xplane = recording.stop() \
            if recording is not None and recording.on else None
        flight = None
        if trace:
            flight = [dict(r, t_s=t_serve + r["t_s"])
                      for r in FLIGHT.snapshot()["records"] if "t_s" in r]
            FLIGHT.disarm()
    peak = memory_peak(cell.chips)
    leak = server.leak_report()
    rep = report.as_dict()
    say({"info": "setup", "imports_s": t_build - t_start,
         "build_s": t_warm - t_build, "warm_s": t_serve - t_warm,
         "prefill_s": source.t_open - t_serve,
         "programs_built": watch.compiled,
         "of_them_from_cache": watch.fetched})
    say({"info": "window", "seconds": source.t_end - source.t_open,
         "requests_released": len(source.recs),
         "outstanding_min": source.outstanding_min,
         "waiting_at_end": source.waiting_at_end,
         "ticks": len(source.polls),
         "stalls": reduce.stalls(source.polls, source.t_open),
         **reduce.halves(source.recs, source.t_open, source.t_end)})

    # Free the program's state before the reference makes its own weights.
    del server, setup, report
    source._server = None
    gc.collect()

    run = Run(cell=cell, seed=seed, slots=slots,
              recs=source.recs, t_open=source.t_open, t_end=source.t_end,
              report=rep, leak=leak, flight=flight,
              trace=recording.reduce(xplane) if xplane else None,
              compile_stamps=watch.stamps, memory_peak_bytes=peak,
              device_kind=device["kind"], peaks=peaks)
    return run, dict(device, memory_peak_bytes=peak), source.t_open - t_start


def judge(run: Run, say, control: Optional[str] = None) -> Dict[str, Any]:
    """The comparison that decides ``correct``: a seeded sample of the
    finished requests against the plain reference, and the exact counts.
    Says every number compared beside its limit."""
    config, ref = run.cell.config, run.cell.reference()
    if control is not None and control not in ref.CONTROLS:
        raise SpecError(f"the {config['family']} reference has no control "
                        f"{control!r} (it has {list(ref.CONTROLS)})")
    t_check = time.monotonic()
    picked = check.sample(run.recs, run.seed,
                          int(config["correct"]["min_tokens"]),
                          int(config["correct"]["max_requests"]))
    weights = ref.init_weights(model_seed(run.seed), ref.Widths.of(config))
    numbers = check.gap_numbers(ref, config, picked, weights)
    leak = run.leak
    numbers["failed_requests"] = reduce.failures(run.recs, run.t_end)["failed"]
    numbers["blocks_leaked"] = leak["blocks_used"] - leak["blocks_cached"] \
        + leak["blocks_private"] + leak["blocks_reserved"] + leak["pins"]
    numbers["compiles_in_window"] = sum(
        1 for s in run.compile_stamps if run.t_open <= s < run.t_end)
    limits = dict(config["correct"]["limits"], failed_requests=0,
                  blocks_leaked=0, compiles_in_window=0)
    ruling = check.verdict(numbers, limits)
    say({"info": "compared", **{k: numbers[k] for k in (
        "tokens_compared", "requests_compared", "longest_compared")},
        "reference_s": time.monotonic() - t_check,
        "rows": ruling["compared"]})
    if control is not None:
        ctl = check.gap_numbers(ref, config, picked, weights,
                                control=control)
        say({"info": "control", "precision": control,
             "gap_max": ctl["gap_max"], "gap_mean": ctl["gap_mean"],
             "correct": check.verdict(ctl, config["correct"]["limits"])
             ["correct"]})
    return ruling


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             say: Callable[[Dict[str, Any]], None] = lambda line: None,
             trace_dir: Optional[str] = None,
             control: Optional[str] = None,
             e2e_all: bool = False) -> Dict[str, Any]:
    """Run the cell once and return the result line as a dict. ``control``
    (``benchmark/calibrate.py`` only) also reads the lower-precision
    control's numbers on the same sample and says them; ``e2e_all``
    (``benchmark/sweep.py`` only) reports every end-to-end metric the
    harness knows, not the cell's own list."""
    run, device, setup_s = measure(
        cell, seed, seconds, trace, t_start=t_start, require_tpu=require_tpu,
        say=say, trace_dir=trace_dir)
    ruling = judge(run, say, control)
    if trace:
        values = {}
        for m in cell.per_layer:
            reader = cell.spec.load_module("layer_metrics", m["name"] + ".py")
            value = reader.read(run)
            if value is not None:
                values[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        known = reduce.end_to_end(run.recs, run.t_open, run.t_end, setup_s)
        values = known if e2e_all else {
            m["name"]: known[m["name"]] for m in cell.end_to_end
            if m["name"] in known}
    fails = reduce.failures(run.recs, run.t_end)
    line: Dict[str, Any] = {
        "correct": ruling["correct"], "attempted": fails["attempted"],
        "failed": fails["failed"], "metrics": values, "device": device,
        "workload": cell.name, "seed": seed,
    }
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        ops, gaps = run.trace["device_ops"], run.trace["idle_gaps"]
        # The result line may carry ten of each; a builder's own traced run
        # reads twenty here (a cost spread over many operations can lie
        # wholly under the cut at ten).
        say({"info": "breakdown", "device_ops": ops[:20],
             "idle_gaps": gaps[:20]})
        line["breakdown"] = {"device_ops": ops[:10], "idle_gaps": gaps[:10]}
    # Last in the line: each number compared beside its limit (a gap where
    # nothing was served is infinite: no JSON number, so null).
    line["compared"] = {
        r["number"]: {"value": r["value"] if math.isfinite(r["value"])
                      else None, "limit": r["limit"]}
        for r in ruling["compared"]}
    return line
