"""Run one benchmark cell once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  The run builds the served RAG
system with the program's own ``repro.launch.serve.build_served``: the
document store, its passages and the weight matrices from the mix's
corpus seed, the norm scales and biases of the configuration's family
(``bench/references/<family>.py``) from ``--seed``.  It warms
up the shapes the mix uses, then a closed loop of clients drives
``RagdollEngine.submit`` to harvest for ``--seconds``, each client
asking the seed's next job when its last one is harvested.  After the
window it frees the program's state and checks what the window served
against the plain reference (``bench/lib/reference.py`` and the
family file).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics read from a profiler trace
of part of the window), ``device`` and, traced, ``breakdown``.  The
numbers the check compared, each with its limit, close standard error
and the result line.  Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse            # noqa: E402
import collections         # noqa: E402
import gc                  # noqa: E402
import json                # noqa: E402
import logging             # noqa: E402
import shutil              # noqa: E402
import sys                 # noqa: E402
import tempfile            # noqa: E402
import threading           # noqa: E402
from pathlib import Path   # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

import numpy as np         # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from lib import reference as R      # noqa: E402
from lib import spec as S           # noqa: E402
from lib import trace as T          # noqa: E402
from lib.traffic import Job, schedule  # noqa: E402

WORK = S.ROOT / ".bench_work"       # traces; listed in .gitignore


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# what the program counts
# ---------------------------------------------------------------------------


class Compiles:
    """Counts XLA compilations and persistent-cache loads as they
    happen, and the names of the jitted functions being compiled."""

    def __init__(self):
        import jax
        self.backend = 0
        self.cache_hits = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)
        jax.config.update("jax_log_compiles", True)
        handler = logging.Handler()
        handler.emit = self._record
        for name in ("jax._src.interpreters.pxla", "jax._src.dispatch",
                     "jax._src.compiler"):
            lg = logging.getLogger(name)
            lg.addHandler(handler)
            lg.propagate = False

    def _dur(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _record(self, rec):
        msg = rec.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg[len("Compiling "):][:160])

    def mark(self):
        return (self.backend, self.cache_hits, len(self.names))


def emitted(engine) -> Dict[int, int]:
    """Tokens emitted so far by every request the engine has seen
    (harvested ones and the ones in slots), read between pump steps."""
    gen = engine.generator
    pump = engine.pipeline.workers[-1]
    lock = getattr(pump, "_lock", None) or threading.Lock()
    with lock:
        for _ in range(20):
            try:
                out = {r.rid: len(r.output.split())
                       for r in list(engine.completed)}
                for ref in gen.table.active_refs():
                    st = gen.table.state(ref)
                    out[st.key.rid] = len(st.tokens)
                return out
            except RuntimeError:      # a table mutated under the read
                continue
    raise RuntimeError("could not read the slot table")


def step_hist(engine):
    h = engine.registry.histogram("decode.step_seconds").to_dict()
    return h["count"], h["sum"]


# ---------------------------------------------------------------------------
# driving the engine
# ---------------------------------------------------------------------------


class Driver:
    """Submits jobs and follows them to harvest."""

    def __init__(self, served, mix: Dict[str, Any], cell_name: str):
        self.served = served
        self.cell_name = cell_name
        self.eng = served.engine
        self.top_k = int(mix["top_k"])
        self.jobs: Dict[int, Job] = {}
        self.reqs: Dict[int, Any] = {}        # rid -> the Request
        self.next_rid = 0

    def submit(self, job: Job) -> int:
        from repro.serving.request import Request
        rid = self.next_rid
        self.next_rid += 1
        self.jobs[rid] = job
        req = Request(rid=rid, query=self.served.queries[job.query],
                      arrival=time.perf_counter(), top_k=self.top_k,
                      max_new_tokens=job.max_new_tokens)
        self.reqs[rid] = req
        self.eng.submit(req)
        return rid

    def done(self) -> Dict[int, Any]:
        return {r.rid: r for r in list(self.eng.completed)}

    def wait_all(self, rids, deadline: float) -> Dict[int, Any]:
        """Wait until every rid in ``rids`` is harvested or the deadline
        passes; returns the harvested requests."""
        want = set(rids)
        while True:
            err = self.eng.pipeline.error()
            if err is not None:
                raise err
            got = self.done()
            if want <= set(got) or time.perf_counter() >= deadline:
                return got
            time.sleep(0.02)

    def closed_loop(self, jobs: List[Job], clients: int,
                    stop: threading.Event) -> threading.Thread:
        """``clients`` clients, each sending its next job the moment its
        previous one is harvested, until ``stop``."""
        it = iter(jobs)

        def run():
            outstanding = set()
            for _ in range(clients):
                outstanding.add(self.submit(next(it)))
            while not stop.is_set():
                err = self.eng.pipeline.error()
                if err is not None:
                    return
                got = self.done()
                for rid in [r for r in outstanding if r in got]:
                    outstanding.discard(rid)
                    if not stop.is_set():
                        outstanding.add(self.submit(next(it)))
                time.sleep(0.005)
        th = threading.Thread(target=run, name="bench-clients", daemon=True)
        th.start()
        return th


def warm_retrieval(served, mix) -> None:
    """The top-k shapes the window can use: every partition (each
    partition size is a shape of its own) for a batch of one query and
    for the largest batch, which the kernel pads to its two row blocks,
    and the merge at both.  (The program re-traces the top-k kernel on
    every call, so a warm-up cannot spare the window those traces.)"""
    store, emb = served.store, served.engine.embedder
    q = emb.embed(served.queries[:int(mix["slots"])])
    # the kernel pads a batch to a multiple of 8 rows
    for n in sorted({1, len(q)} if len(q) > 8 else {1}):
        store.search(q[:n], int(mix["top_k"]))


def warm_generation(served, mix) -> None:
    """Prefill and decode at the cell's shapes, straight on the
    generator before the engine starts: two batch-1 prefills into the
    slot table and decode steps at its full width."""
    gen = served.generator
    prompt = " ".join(served.store.chunks[i]
                      for i in range(int(mix["top_k"])))
    for i in range(2):
        if gen.join(("warm-up", i), f"{prompt} {served.queries[i]}",
                    3) is None:
            raise RuntimeError("the warm-up could not join a slot")
    while gen.active_slots:
        gen.step()
    gen.harvest()


def warm_engine(drv: "Driver", mix, seed: int) -> None:
    """A few short requests through the engine itself, so that the
    retrieval and generation calls of the window find their compiled
    programs in the persistent cache (a first run in a new checkout
    compiles them here, in set-up)."""
    n = min(int(mix["clients"]), 8)
    jobs = schedule(mix, seed, 0, n, int(mix["query_pool"]))
    now = time.perf_counter()
    rids = [drv.submit(Job(j.query, 2)) for j in jobs]
    got = drv.wait_all(rids, now + 900.0)
    if not set(rids) <= set(got):
        raise RuntimeError("warm-up requests did not finish in 900 s")


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def written(tree: Dict[str, Any], new: Dict[str, Any], where: str
            ) -> Dict[str, Any]:
    """``tree`` with every leaf of ``new`` put at the same path, each into
    the memory the program keeps the old leaf in (device, or pinned host
    for a streamed layer).  A path ``tree`` lacks, or a leaf of another
    shape or type, is an error: a seeded value never goes unwritten."""
    import jax
    out = dict(tree)
    for key, val in new.items():
        at = f"{where}/{key}"
        if key not in out:
            raise KeyError(f"the served weights have no {at}")
        if isinstance(val, dict):
            out[key] = written(out[key], val, at)
            continue
        old = out[key]
        if (val.shape, val.dtype) != (old.shape, old.dtype):
            raise ValueError(f"{at} is {old.dtype}{list(old.shape)} served, "
                             f"{val.dtype}{list(val.shape)} seeded")
        out[key] = jax.device_put(val, old.sharding)
    return out


def set_scales(served, sc: Dict[str, Any]) -> None:
    """Write the family's seeded tree (``fam.scales``: ``top`` and one
    tree per layer) into the served weights."""
    import jax
    ex = served.generator.exec
    if len(sc["layers"]) != len(ex.layers):
        raise ValueError(f"{len(sc['layers'])} layers seeded, "
                         f"{len(ex.layers)} served")
    ex.top = written(ex.top, sc["top"], "top")
    for i, ((kind, lp), new) in enumerate(zip(ex.layers, sc["layers"])):
        ex.layers[i] = (kind, written(lp, new, f"layers/{i}"))
    jax.block_until_ready((ex.top, ex.layers))


def model_config(m: Dict[str, Any]):
    """The program's ``ModelConfig`` of a configuration's ``model``
    section; its ``moe``, ``mla`` and ``ssm`` sections become their own
    configs, and any other section is an error."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, \
        SSMConfig
    sections = {"moe": MoEConfig, "mla": MLAConfig, "ssm": SSMConfig}
    kw = dict(m)
    kw["layer_pattern"] = tuple(tuple(k) for k in kw["layer_pattern"])
    for key, val in kw.items():
        if isinstance(val, dict):
            if key not in sections:
                raise ValueError(f"model section {key!r} is not one of "
                                 f"{sorted(sections)}")
            kw[key] = sections[key](**val)
    return ModelConfig(**kw)


def device_info():
    import jax
    devs = jax.devices()
    dev = devs[0]
    stats = dev.memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak),
            "bytes_limit": int(stats.get("bytes_limit", 0))}


def check_chip(chips: int) -> None:
    import jax
    try:
        devs = jax.devices("tpu")
    except RuntimeError as e:
        raise NoChip(f"JAX finds no TPU: {e}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX finds "
                     f"{len(devs)}")


def sample_for_check(served: List[tuple], seed: int,
                     check: Dict[str, int]) -> List[tuple]:
    """Answers the reference re-runs, each ``(query, ids, tokens)``: the
    longest, then others drawn from the seed, until ``check["tokens"]``
    served tokens or ``check["requests"]`` answers."""
    if not served:
        return []
    rng = np.random.default_rng([seed, 3])
    longest = max(range(len(served)), key=lambda i: len(served[i][2]))
    rest = [a for i, a in enumerate(served) if i != longest]
    order = [served[longest]] + [rest[i]
                                 for i in rng.permutation(len(rest))]
    out, toks = [], 0
    for a in order:
        if len(out) >= check["requests"] or toks >= check["tokens"]:
            break
        out.append(a)
        toks += len(a[2])
    return out


def run(name: str, seed: int, seconds: float, trace: bool, *,
        cell: Optional[Dict] = None, conf: Optional[Dict] = None,
        mix: Optional[Dict] = None, require_chip: bool = True,
        build_kw: Optional[Dict] = None, tamper=None,
        control: bool = False) -> Dict[str, Any]:
    """One run of cell ``name``; returns the result object.

    ``cell``, ``conf`` and ``mix`` replace what ``BENCHMARK.json`` and
    the data files say, and ``require_chip=False`` skips the look for a
    TPU: the tests drive a tiny stand-in of a cell on the CPU that way.
    ``build_kw`` adds arguments to ``build_served``; ``tamper(served)``
    may break the served system before the window.  ``control`` also
    reads the control's numbers (see ``check``).
    """
    cell = cell or S.cell(name)
    conf = conf or S.config(cell["config"])
    mix = mix or S.traffic(cell["traffic"])
    import jax
    if require_chip:
        check_chip(int(cell["chips"]))
    dev = jax.devices()[0]
    peaks = S.peaks(dev.device_kind) if require_chip else None

    sys.path.insert(0, str(S.ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import build_served
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = Compiles()

    m = conf["model"]
    cfg = model_config(m)
    fam = S.reference(conf["reference"])
    corpus = mix["corpus"]
    if corpus["spilled"] != corpus["partitions"] // 2:
        raise ValueError("build_served spills half the partitions")
    cap = int(mix["answer"]["max"])
    slots = int(mix["slots"])
    pool = int(mix["query_pool"])
    log(f"cell {name}: {cell['config']} x {cell['traffic']}, seed {seed}, "
        f"{seconds} s, trace {int(trace)}; device {dev.device_kind} "
        f"x{len(jax.devices())}; compile cache {cache_dir}")

    result: Dict[str, Any] = {}
    with tempfile.TemporaryDirectory() as spill:
        served = build_served(
            cfg, spill_root=spill, seed=int(corpus["seed"]), num_slots=slots,
            ctx_len=int(mix["ctx_len"]), max_new_tokens=cap,
            page_size=int(mix["page_size"]), corpus_size=corpus["rows"],
            dim=corpus["dim"], partitions=corpus["partitions"],
            num_queries=pool, log=log, **(build_kw or {}))
        served.store.chunks = R.Passages(corpus["rows"], int(corpus["seed"]),
                                         int(corpus["passage_words"]))
        set_scales(served, fam.scales(m, seed))
        ex = served.generator.exec
        log(f"built: {served.seconds}; {ex.resident}/{ex.n_layers} layers "
            f"resident, {ex.streamed_bytes} B streamed per step; "
            f"{served.plan.kv_pages} KV pages")
        if tamper is not None:
            tamper(served)
        drv = Driver(served, mix, name)
        eng = served.engine
        t = time.perf_counter()
        warm_retrieval(served, mix)
        log(f"warm-up retrieval {time.perf_counter() - t:.1f} s")
        t = time.perf_counter()
        warm_generation(served, mix)
        log(f"warm-up generation {time.perf_counter() - t:.1f} s")
        eng.start()
        stop = threading.Event()
        try:
            t = time.perf_counter()
            warm_engine(drv, mix, seed)
            log(f"warm-up through the engine {time.perf_counter() - t:.1f} s")
            result = window(drv, mix, seed, seconds, trace, compiles,
                            stop, conf, fam, peaks)
        finally:
            stop.set()
            eng.stop()
        result["device"] = device_info() if require_chip else {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": 0}
        sample = sample_for_check(result.pop("_served"), seed,
                                  mix["check"])
        retr = result.pop("_retrieved")
        del served, drv, eng, ex
    gc.collect()
    log(f"device bytes in use before the reference: "
        f"{(dev.memory_stats() or {}).get('bytes_in_use')}")
    t = time.perf_counter()
    numbers = check(conf, mix, seed, sample, retr, control)
    log(f"reference check {time.perf_counter() - t:.1f} s over "
        f"{len(sample)} requests, {len(retr)} retrievals")
    correct = all(v <= lim for k, (v, lim) in numbers.items()
                  if not k.startswith("control."))
    result["correct"] = bool(correct and result["failed"] == 0)
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in numbers.items()}
    for k, (v, lim) in numbers.items():
        log(f"compared {k}: {v!r} (limit {lim!r})")
    return result


def window(drv: Driver, mix, seed, seconds, trace, compiles, stop, conf,
           fam, peaks) -> Dict[str, Any]:
    eng = drv.eng
    preroll = float(mix["preroll_s"])
    t0 = time.perf_counter() + preroll
    t1 = t0 + seconds
    jobs = schedule(mix, seed, 2, 4096, int(mix["query_pool"]))
    drv.closed_loop(jobs, int(mix["clients"]), stop)

    time.sleep(max(t0 - time.perf_counter(), 0.0))
    setup_s = time.perf_counter() - T_START
    c0 = compiles.mark()
    e0, h0 = emitted(eng), step_hist(eng)
    traced = None
    if trace:
        traced = traced_part(eng, conf, mix, t0, t1)
    time.sleep(max(t1 - time.perf_counter(), 0.0))
    e1, h1 = emitted(eng), step_hist(eng)
    c1 = compiles.mark()
    # clients send nothing more; the window's requests are those in
    # generation at some time in it (queued ones were never started)
    stop.set()
    window_rids = [
        rid for rid, r in list(drv.reqs.items())
        if r.t_gen_start is not None and r.t_gen_start < t1
        and (r.t_gen_end is None or r.t_gen_end >= t0)]
    grace = float(mix["grace_s"])
    got = drv.wait_all(window_rids, t1 + grace)
    finished = [got[r] for r in window_rids if r in got]
    failed = len(window_rids) - len(finished)
    tokens = sum(max(e1.get(r, 0) - e0.get(r, 0), 0) for r in e1)
    log(f"window: {len(window_rids)} requests in generation, "
        f"{len(finished)} finished, {failed} not within {grace:.0f} s of "
        f"the close; {tokens} tokens")
    names = collections.Counter(compiles.names[c0[2]:c1[2]])
    dev_stats = eng.generator.exec.device.memory_stats() or {}
    log(f"device bytes in use at the close: "
        f"{dev_stats.get('bytes_in_use')}, peak "
        f"{dev_stats.get('peak_bytes_in_use')}")
    log(f"compiles in window: {c1[2] - c0[2]} ({c1[0] - c0[0]} by the "
        f"backend, {c1[1] - c0[1]} loaded from the cache); most often: "
        f"{names.most_common(8)}")

    e2e = {"setup_s": (setup_s, "s"),
           "tokens_per_s": (tokens / seconds, "tokens/s")}
    ctx = {
        "model": conf["model"], "reference": fam, "mix": mix,
        "peaks": peaks,
        "requests": finished, "seconds": seconds,
        "steps": (h1[0] - h0[0], h1[1] - h0[1]),
        "streamed_bytes": int(eng.generator.exec.streamed_bytes),
        "trace": traced,
    }
    if trace:
        metrics = {}
        for m in S.metrics_of(drv.cell_name, "per_layer"):
            val = S.metric_reader(m["name"]).read(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]][0]),
                               "unit": m["unit"]}
                   for m in S.metrics_of(drv.cell_name, "end_to_end")}
    served = [(drv.jobs[r.rid].query, [R.Passages.id_of(c)
                                       for c in r.retrieved],
               [int(t[3:]) for t in r.output.split()]) for r in finished]
    out = {"attempted": len(window_rids), "failed": failed,
           "metrics": metrics, "_served": served,
           "_retrieved": [(q, ids) for q, ids, _ in served]}
    if traced is not None:
        out["breakdown"] = traced["breakdown"]
        out["_device_busy"] = (traced["busy_s"], traced["window_s"])
    return out


def traced_part(eng, conf, mix, t0, t1) -> Dict[str, Any]:
    """Profile the middle ``trace_s`` of the window; reduce the trace and
    the host counts over the same span."""
    import jax
    from repro.obs.trace import Tracer
    span = min(float(mix["trace_s"]), t1 - t0)
    ts = t0 + (t1 - t0 - span) / 2
    tracer = Tracer(capacity=1 << 20)
    eng.tracer = tracer
    eng.streamer.tracer = tracer
    eng.scheduler.tracer = tracer
    eng.generator.bind_obs(tracer, None)
    out_dir = WORK / "trace"
    shutil.rmtree(out_dir, ignore_errors=True)
    time.sleep(max(ts - time.perf_counter(), 0.0))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation(T.MARKER):
        tracer.instant(T.MARKER)
        a = time.perf_counter()
    e0, j0 = emitted(eng), eng.generator.joins
    time.sleep(max(ts + span - time.perf_counter(), 0.0))
    e1, j1 = emitted(eng), eng.generator.joins
    b = time.perf_counter()
    jax.profiler.stop_trace()
    path = T.find(str(out_dir))
    # the window and the host spans, on the trace clock via the marker
    red = T.reduce(path, after_marker=(0.0, b - a))
    with open(WORK / "trace_ops.json", "w") as f:
        json.dump(sorted(([n, o.seconds, o.count, o.text]
                          for n, o in red.ops.items()),
                         key=lambda r: -r[1]), f, indent=0)
    mark_us = next(e[2] for e in tracer.events() if e[1] == T.MARKER)
    spans, open_ = [], {}
    for ph, name, ts_us, tid, _aid, _attrs in tracer.events():
        t_ns = red.marker_ns + (ts_us - mark_us) * 1e3
        if ph == "B":
            open_.setdefault(tid, []).append((name, t_ns))
        elif ph == "E" and open_.get(tid):
            nm, s_ns = open_[tid].pop()
            spans.append((s_ns, t_ns, nm))
    ctx_len = int(mix["ctx_len"])
    decode_kv = []
    for rid, n1 in e1.items():
        n0 = e0.get(rid, 0)
        for j in range(max(n0 + 1, 2), n1 + 1):
            decode_kv.append(ctx_len + j - 1)
    return {"window_s": red.window_s, "busy_s": red.busy_s,
            "reduced": red, "joins": j1 - j0, "decode_kv": decode_kv,
            "ctx_len": ctx_len,
            "breakdown": {"device_ops": T.top_ops(red),
                          "idle_gaps": T.label_gaps(red, spans)}}


def check(conf, mix, seed, sample, retrieved, control=False
          ) -> Dict[str, tuple]:
    """The numbers ``correct`` compares, each as (value, limit).
    ``sample`` holds ``(query, ids, tokens)`` of answers, ``retrieved``
    ``(query, ids)`` of every finished request.  With ``control`` it adds
    the same numbers for the control: retrieval scored in bfloat16, and
    the tokens an fp8 forward puts first."""
    m = conf["model"]
    fam = S.reference(conf["reference"])
    lim = conf["limits"]
    corpus = mix["corpus"]
    cseed = int(corpus["seed"])
    t = time.perf_counter()
    vecs = R.blob_corpus(corpus["rows"], corpus["dim"],
                         corpus["partitions"], cseed)
    qpool = R.perturbed_queries(vecs, int(mix["query_pool"]), cseed + 1)
    if retrieved:
        rgap = R.retrieval_gap(vecs, qpool[[q for q, _ in retrieved]],
                               [ids for _, ids in retrieved],
                               int(mix["top_k"]))
    else:
        rgap = float("inf")
    log(f"reference retrieval {time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    passages = R.Passages(corpus["rows"], cseed,
                          int(corpus["passage_words"]))
    prompts = [R.tokenize(" ".join(passages[i] for i in ids)
                          + f" {corpus['rows'] + q}", int(mix["ctx_len"]),
                          m["vocab_size"]) for q, ids, _ in sample]
    served = [toks for _, _, toks in sample]
    cap = int(mix["answer"]["max"])
    seeds = (cseed, seed)
    tgap = (R.token_gap(fam, m, seeds, prompts, served, max_new=cap)
            if sample else float("inf"))
    log(f"reference forward {time.perf_counter() - t:.1f} s")
    out = {"retrieval_gap": (rgap, lim["retrieval_gap"]),
           "token_logit_gap": (tgap, lim["token_logit_gap"])}
    if control and retrieved and sample:
        out["control.retrieval_gap"] = (R.retrieval_gap(
            vecs, qpool[[q for q, _ in retrieved]], None,
            int(mix["top_k"]), lowp=True), lim["retrieval_gap"])
        out["control.token_logit_gap"] = (R.token_gap(
            fam, m, seeds, prompts, served, precision="fp8", max_new=cap),
            lim["token_logit_gap"])
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control's numbers (not part of "
                         "a benchmark run)")
    args = ap.parse_args()
    try:
        res = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  control=bool(args.control))
    except NoChip as e:
        log(f"no result: {e}")
        return 3
    busy = res.pop("_device_busy", None)
    if busy is not None:
        res["device"]["busy_s"], res["device"]["window_s"] = busy
    compared = res.pop("compared")
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": res["metrics"],
           "device": res["device"]}
    if "breakdown" in res:
        out["breakdown"] = res["breakdown"]
    out["compared"] = compared
    for k, v in compared.items():
        log(f"{k}: {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
