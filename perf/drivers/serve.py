"""Driver of serving cells: the model behind ``InferStep`` and the default
batcher, offered a closed loop of callers, timed from the client's side.

What it takes from the configuration: ``program``, ``precision``,
``serving`` (slots, pages, buckets, limits), ``check``, ``tolerance``. From
the traffic mix: ``clients``, the length distributions, ``drain_s``.

Order of a run: seeded weights (the reference's generator) given to the
program -> engine and batcher built and warmed -> the callers start and
each finishes one request (the ramp, set-up) -> window -> drain -> the
program is stopped and freed -> the plain reference runs once over a seeded
sample of the requests the window finished, the longest among them, and the
served tokens are held against its logits.
"""

import gc
import importlib
import threading
import time

import numpy as np

from perf.harness import traffic as gen
from perf.harness.clock import per_token_gap, percentile
from perf.harness.main import Run

BOS = 1


def _build_program(cfg, weights):
    """The system under test as ``chip_smoke.phase_serve`` builds it: the
    zoo's model, given the seeded weights, behind ``InferStep`` and
    ``make_batcher`` with default gates; no ``MXTPU_*`` variable is set."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.parallel import InferStep
    from mxnet_tpu.serving import make_batcher

    prog, srv = cfg["program"], cfg["serving"]
    mod, cls = prog["model"].split(":")
    kwargs = {k: cfg[v] for k, v in prog["kwargs"].items()}
    net = getattr(importlib.import_module(mod), cls)(**kwargs)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    params = net._collect_params_with_prefix()
    if set(params) != set(weights):
        raise SystemExit("perf: the program's parameters and the reference's "
                         f"differ: {sorted(set(params) ^ set(weights))[:6]}")
    for name, p in params.items():
        p.set_data(nd.NDArray(weights[name]))
    eng = InferStep(net, amp=cfg["precision"]["weights"],
                    max_len=srv["max_len"])
    bat = make_batcher(eng, srv["prompt_buckets"], slots=srv["slots"],
                       max_new_tokens=srv["max_new_tokens"],
                       page_size=srv["page_size"],
                       num_pages=srv.get("num_pages"),  # None: its default
                       max_prefix_tokens=srv["max_prefix_tokens"],
                       warmup=True, name="perf")
    return net, eng, bat


class Record:
    __slots__ = ("index", "prompt", "max_new", "sent", "first", "last",
                 "chunks", "tokens", "error", "queue_wait_ms")

    def __init__(self, index, prompt, max_new):
        self.index, self.prompt, self.max_new = index, prompt, max_new
        self.sent = self.first = self.last = None
        self.chunks, self.tokens, self.error = [], None, None
        self.queue_wait_ms = None


class ClosedLoop:
    """``clients`` callers; each sends its next request when the last one
    resolved, and none sends after ``close()``."""

    def __init__(self, bat, stream, clients, chunk_timeout, span):
        self.bat, self.stream, self.span = bat, stream, span
        self.chunk_timeout = chunk_timeout
        self.lock = threading.Lock()
        self.next_index = 0
        self.records = []
        self.done_by_client = [0] * clients
        self.closed = threading.Event()
        self.threads = [threading.Thread(target=self._client, args=(c,),
                                         name=f"perf-client-{c}", daemon=True)
                        for c in range(clients)]

    def start(self):
        for t in self.threads:
            t.start()

    def close(self):
        self.closed.set()

    def join(self, timeout):
        """Wait for every caller's last request; True when all ended."""
        deadline = time.perf_counter() + timeout
        for t in self.threads:
            t.join(max(0.0, deadline - time.perf_counter()))
        return not any(t.is_alive() for t in self.threads)

    def _client(self, c):
        while not self.closed.is_set():
            with self.lock:
                i = self.next_index
                self.next_index += 1
            prompt, max_new = self.stream.request(i)
            rec = Record(i, prompt, max_new)
            with self.lock:
                self.records.append(rec)
            rec.sent = time.perf_counter()
            try:
                with self.span("submit"):
                    fut = self.bat.submit(prompt, max_new_tokens=max_new)
                for chunk in fut.tokens_iter(timeout=self.chunk_timeout):
                    now = time.perf_counter()
                    if rec.first is None:
                        rec.first = now
                    rec.last = now
                    rec.chunks.append((now, len(chunk)))
                rec.tokens = [int(t) for t in fut.result(timeout=0)]
                rec.queue_wait_ms = fut.queue_wait_ms
            except Exception as e:  # noqa: BLE001 - a failed request counts
                rec.error = repr(e)
            self.done_by_client[c] += 1


def _check_sample(records, cfg, seed):
    """A seeded sample of finished requests with the longest in it."""
    ok = [r for r in records if r.error is None and r.tokens]
    if not ok:
        return []
    n = min(int(cfg["check"]["sample_requests"]), len(ok))
    longest = max(ok, key=lambda r: (len(r.tokens), -r.index))
    rest = [r for r in ok if r is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, 7])
    picked = [rest[j] for j in rng.permutation(len(rest))[:n - 1]]
    return [longest] + picked


def _padded(sample, pad_to):
    B = len(sample)
    src = np.zeros((B, pad_to), np.int32)
    src_len = np.zeros((B,), np.int32)
    tgt_in = np.zeros((B, pad_to), np.int32)
    served = np.zeros((B, pad_to), np.int32)
    n = np.zeros((B,), np.int32)
    for b, r in enumerate(sample):
        src[b, :len(r.prompt)] = r.prompt
        src_len[b] = len(r.prompt)
        toks = r.tokens[:pad_to]
        n[b] = len(toks)
        tgt_in[b, 0] = BOS
        tgt_in[b, 1:len(toks)] = toks[:-1]
        served[b, :len(toks)] = toks
    return src, src_len, tgt_in, served, n


def widest_gap(ref, weights, sample, cfg, quant=None):
    """The widest gap, over every served position of the sample, by which
    the served token's logit lies below the reference's best; with
    ``quant`` the control's tokens stand in for the served ones."""
    pad_to, batch = int(cfg["check"]["pad_to"]), int(cfg["check"]["batch"])
    worst, positions = 0.0, 0
    for at in range(0, len(sample), batch):
        part = sample[at:at + batch]
        while len(part) < batch:       # one compiled shape
            part = part + [part[-1]]
        src, src_len, tgt_in, served, n = _padded(part, pad_to)
        gaps = np.asarray(ref.served_token_gaps(
            weights, src, src_len, tgt_in, served, cfg, quant))
        live = np.arange(pad_to)[None, :] < n[:, None]
        g = np.where(live, gaps, 0.0)
        worst = max(worst, float(g.max())) if np.isfinite(g).all() \
            else float("nan")
        positions += int(live[:min(batch, len(sample) - at)].sum())
    return worst, positions


def _serve(ctx, cfg, mix, run, weights):
    """Build, warm, ramp, window, drain, stop. Returns the records."""
    import jax

    t_build = time.perf_counter()
    net, eng, bat = _build_program(cfg, weights)
    del weights
    t_ramp = time.perf_counter()
    stream = gen.RequestStream(mix, ctx.seed, cfg["vocab_size"])
    drain_s = float(mix["drain_s"])
    loop = ClosedLoop(bat, stream, int(mix["clients"]), drain_s,
                      ctx.tracer.span)
    loop.start()
    ramp_deadline = time.perf_counter() + drain_s
    while min(loop.done_by_client) < 1:   # the ramp: every slot refilled once
        if time.perf_counter() > ramp_deadline:
            raise SystemExit("perf: the ramp did not finish in drain_s")
        time.sleep(0.01)
    ctx.say("setup_parts", before_build=t_build - ctx.process_start,
            build_and_warm_up=t_ramp - t_build,
            ramp=time.perf_counter() - t_ramp,
            warmup_programs=eng.compile_guard.signatures)
    run.compiles_before_window = ctx.compiles.count
    stats0 = dict(bat.stats)
    run.window_start = t0 = time.perf_counter()
    ctx.memory.sample("window_open")
    if ctx.trace:
        after, length = ctx.tracer.stretch(ctx.seconds)
        time.sleep(after)
        ctx.tracer.start()
        time.sleep(length)
        ctx.tracer.stop()
    time.sleep(max(0.0, t0 + ctx.seconds / 2 - time.perf_counter()))
    ctx.memory.sample("mid_window")
    time.sleep(max(0.0, t0 + ctx.seconds - time.perf_counter()))
    loop.close()
    t1 = time.perf_counter()
    stats1 = dict(bat.stats)
    ctx.memory.sample("window_close")
    run.compiles_in_window = ctx.compiles.count - run.compiles_before_window
    run.window_s = t1 - t0
    all_ended = loop.join(drain_s)
    # the scheduler retires on its own thread: give it a moment to hand the
    # last pages back before they are counted
    pool = bat.pool
    deadline = time.perf_counter() + 10
    while pool.free_pages + bat.cache.total_pages != pool.num_pages \
            and time.perf_counter() < deadline:
        time.sleep(0.01)
    kept = bat.cache.total_pages
    unaccounted = pool.num_pages - pool.free_pages - kept
    pages_ok = unaccounted == 0
    bat.stop()
    recompiles = eng.compile_guard.steady_state_recompiles
    pages_back = pool.free_pages == pool.num_pages
    pool.check_invariants(set())
    ctx.say("compared", number="steady_state_recompiles", value=recompiles,
            limit=0, inside=recompiles == 0)
    ctx.say("compared", number="pages_unaccounted_after_drain",
            value=unaccounted, limit=0,
            inside=pages_ok, kept_by_prefix_trie=kept)
    ctx.say("compared", number="pages_not_back_after_stop",
            value=pool.num_pages - pool.free_pages, limit=0, inside=pages_back)
    ctx.say("compared", number="callers_ended_in_drain", value=all_ended,
            inside=all_ended)
    run.correct = (recompiles == 0 and pages_ok and pages_back and all_ended)
    run.obs.update(stats0=stats0, stats1=stats1, t0=t0, t1=t1,
                   slots=cfg["serving"]["slots"],
                   iter_tokens=bat.iter_tokens)
    records = list(loop.records)
    # free the program before the reference runs, so that the device's peak
    # stays the program's
    del loop, bat, eng, net
    gc.collect()
    jax.clear_caches()
    return records


def _measure(ctx, mix, run, records):
    t0, t1 = run.obs["t0"], run.obs["t1"]
    drain_ms = float(mix["drain_s"]) * 1e3
    window = [r for r in records if r.sent is not None and t0 <= r.sent < t1]
    ok = [r for r in window if r.error is None and r.tokens]
    run.attempted = len(window)
    run.failed = len(window) - len(ok)
    tokens_in_window = sum(n for r in records if r.error is None
                           for (t, n) in r.chunks if t0 <= t <= t1)
    # a failed request misses every limit: it stands at the drain's length
    ttft = [(r.first - r.sent) * 1e3 for r in ok] + [drain_ms] * run.failed
    tpot = [g * 1e3 for g in (per_token_gap(r.first, r.last, len(r.tokens))
                              for r in ok) if g is not None] \
        + [drain_ms] * run.failed
    ctx.say("samples", attempted=run.attempted, failed=run.failed,
            ttft_samples=len(ttft), tpot_samples=len(tpot),
            tokens_in_window=tokens_in_window, window_s=run.window_s,
            requests_finished=len(ok),
            ttft_p50_ms=percentile(ttft, 50), tpot_p50_ms=percentile(tpot, 50),
            errors=sorted({r.error for r in window if r.error})[:3])
    if not ttft or not tpot:
        raise SystemExit("perf: the window finished no request")
    run.e2e = {
        "serve_tokens_per_s": (tokens_in_window / run.window_s, "tokens/s"),
        "ttft_p95_ms": (percentile(ttft, 95), "ms"),
        "tpot_p95_ms": (percentile(tpot, 95), "ms"),
    }
    run.obs["queue_wait_ms"] = [r.queue_wait_ms for r in ok
                                if r.queue_wait_ms is not None]
    return ok


def run(ctx, with_control=False):
    import jax

    import mxnet_tpu as mx

    mx.telemetry.disable()  # telemetry/events.jsonl is a tracked file
    cfg, mix = ctx.config, ctx.traffic
    if mix["kind"] != "closed_loop":
        raise SystemExit(f"perf: no sender for traffic kind {mix['kind']!r} "
                         "yet (PERF.md, Open questions)")
    run = Run()
    ref = ctx.bench.reference(cfg["name"])
    records = _serve(ctx, cfg, mix, run, ref.init_params(ctx.seed, cfg))
    finished = _measure(ctx, mix, run, records)

    # ---- the served tokens against the plain reference
    t = time.perf_counter()
    sample = _check_sample(finished, cfg, ctx.seed)
    weights = ref.init_params(ctx.seed, cfg)
    gap, positions = widest_gap(ref, weights, sample, cfg)
    limit = cfg["tolerance"]["widest_logit_gap"]
    inside = bool(gap <= limit)
    ctx.say("compared", number="widest_logit_gap", value=gap, limit=limit,
            inside=inside, requests=len(sample), positions=positions,
            longest=len(sample[0].tokens) if sample else 0,
            reference_s=time.perf_counter() - t)
    run.correct = run.correct and inside and run.failed == 0 and positions > 0
    if with_control:
        gap, positions = widest_gap(ref, weights, sample, cfg,
                                    quant=cfg["control"])
        run.control_inside = bool(gap <= limit)
        ctx.say("compared", number="widest_logit_gap", of="control",
                value=gap, limit=limit, inside=run.control_inside,
                positions=positions)
    del weights
    jax.clear_caches()
    return run


def control(ctx):
    """The control: a short run of the program at the cell's own load, and
    then, at each position of the same prompts and served tokens, the token
    that the reference computed in float8 puts first, held against the
    float32 reference. It has to fall outside the limit; the program's own
    reading is printed beside it."""
    return not run(ctx, with_control=True).control_inside
