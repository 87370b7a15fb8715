"""The serving driver: an asyncio load generator in the process that holds
the chip, sending the mix's requests through ``ServingEngine.submit`` and
reading the token streams as a client does. Closed loop (each client sends
its next request when the last one ends) and open loop (requests sent when
due, whatever the server does) are both here.

The window is made of whole scheduler steps: it opens at the end of the
step during which warm-up completed and closes at the end of the first step
that ends ``--seconds`` or more later. Rates are all tokens of those steps
over all that time; a step today takes seconds, so a window cut by the
clock alone would count a step more or less from run to run."""
import asyncio
import gc
import time

import numpy as np

from . import common, correct, traffic


class Recorder:
    """Pump-thread side: what each scheduler step fed and produced. The
    sampler hands it every sampled row's logits; ``on_token`` ties the
    sample to its request."""

    def __init__(self, window_tokens):
        self.window_tokens = window_tokens or (1 << 62)
        self.step_no = 0
        self.steps = []                 # per step: dict of counts and times
        self.n_seen = {}                # req -> tokens committed so far
        self.gen_step = {}              # req -> [step of each generated tok]
        self.top_logit = {}             # req -> [program's logit of the tok]
        self._last_top = None
        self._cur = self._zero()
        self._touched = {}
        self.armed = False
        self.seconds = None
        self.t0 = self.t1 = None        # perf_counter at the window's ends
        self.t0_ns = self.t1_ns = None
        self.first_step = self.last_step = None
        self.on_close = None
        self.sync_ns = None             # host clock at the trace's mark

    @staticmethod
    def _zero():
        return {"tokens_fed": 0, "rows_sampled": 0, "ctx_fed": 0}

    def sampler(self, logits):
        tok = int(np.argmax(logits))
        self._last_top = float(logits[tok])
        return tok

    def on_token(self, req, tok, is_prompt):
        rid, w = req.req_id, self.window_tokens
        seen = self.n_seen.get(rid, 0)
        c = self._cur
        if is_prompt:
            seen += 1
            c["tokens_fed"] += 1
            c["ctx_fed"] += min(seen, w)
        else:
            gens = self.gen_step.setdefault(rid, [])
            if gens:                     # a decode row fed its last token
                c["tokens_fed"] += 1
                c["ctx_fed"] += min(seen, w)
            c["rows_sampled"] += 1
            gens.append(self.step_no)
            self.top_logit.setdefault(rid, []).append(self._last_top)
            self._touched[rid] = min(seen, w)
            seen += 1
        self.n_seen[rid] = seen
        if is_prompt:
            self._touched[rid] = min(seen, w)

    def step_end(self, ev, pool_used):
        now, now_ns = time.perf_counter(), time.time_ns()
        c = self._cur
        c.update(end=now, end_ns=now_ns, rows=len(self._touched),
                 ctx_rows=sum(self._touched.values()), pool_used=pool_used,
                 prefill=ev.get("prefill_tokens", 0),
                 decode=ev.get("decode_tokens", 0))
        self.steps.append(c)
        self._cur, self._touched = self._zero(), {}
        if self.armed and self.t0 is None:
            self.t0, self.t0_ns = now, now_ns
            self.first_step = self.step_no + 1
        elif self.t0 is not None and self.t1 is None \
                and now - self.t0 >= self.seconds:
            self.t1, self.t1_ns = now, now_ns
            self.last_step = self.step_no
            if self.on_close:
                self.on_close()
        self.step_no += 1


def _build(family, config, seed, sampler):
    from paddle_tpu.inference import BatchScheduler

    model, _ = common.build_model(family, config, seed)
    model.eval()
    adapter = family.serving(model, config)
    sched = BatchScheduler(adapter, sampler=sampler,
                           **config["program"]["scheduler"])
    return model, adapter, sched


async def _load(engine, reqs, mix, rec, seconds, trace, trace_dir, notes):
    """Drive the mix. Returns {req_id: [arrival time of each token]},
    {req_id: [token]}, the ids of requests that failed, and how late the
    open loop sent each request."""
    from paddle_tpu.inference import Request

    loop = asyncio.get_running_loop()
    arrivals, tokens_of, failed = {}, {}, []
    closing = asyncio.Event()
    closed_by_pump = asyncio.Event()
    rec.on_close = lambda: loop.call_soon_threadsafe(closed_by_pump.set)
    first_tokens = set()
    warm = asyncio.Event()
    n_clients = int(mix.get("clients", 0))
    late = []

    async def one(q):
        req = Request(q["id"], list(q["prompt"]), max_new_tokens=q["max_new"],
                      on_token=rec.on_token)
        times = arrivals.setdefault(q["id"], [])
        toks = tokens_of.setdefault(q["id"], [])
        try:
            stream = await engine.submit(req)
            async for tok in stream:
                times.append(time.perf_counter())
                toks.append(int(tok))
                if len(times) == 1:
                    first_tokens.add(q.get("client", q["id"]))
                    if mix["loop"] == "closed" and \
                            len(first_tokens) >= n_clients:
                        warm.set()
            if not closing.is_set() and len(times) < q["max_new"]:
                failed.append(q["id"])
        except Exception as e:                      # refused or engine closed
            if not closing.is_set():
                failed.append(q["id"])
                notes.append(f"{q['id']}: {type(e).__name__}: {e}")

    async def client(c):
        for q in (r for r in reqs if r["client"] == c):
            if closing.is_set():
                return
            await one(q)

    async def open_loop():
        t_first = time.perf_counter()
        tasks = []
        for q in reqs:
            wait = t_first + q["due_s"] - time.perf_counter()
            if wait > 0:
                await asyncio.sleep(wait)
            if closing.is_set():
                break
            late.append(time.perf_counter() - (t_first + q["due_s"]))
            tasks.append(asyncio.ensure_future(one(q)))
        await asyncio.gather(*tasks)

    if mix["loop"] == "closed":
        tasks = [asyncio.ensure_future(client(c)) for c in range(n_clients)]
    else:
        tasks = [asyncio.ensure_future(open_loop())]
    wu = mix.get("warmup")
    if isinstance(wu, dict):
        await asyncio.sleep(float(wu["seconds"]))
    else:
        await warm.wait()
    if trace:
        rec.sync_ns = common.start_trace(trace_dir)
    rec.seconds = seconds
    rec.armed = True                       # the window opens at a step's end
    await closed_by_pump.wait()
    # every token of the window's steps reaches its client before the close
    want = {rid: sum(1 for s in st if s <= rec.last_step)
            for rid, st in list(rec.gen_step.items())}
    t_wait = time.perf_counter()
    while time.perf_counter() - t_wait < 5.0 and any(
            len(arrivals.get(rid, ())) < n for rid, n in want.items()):
        await asyncio.sleep(0.002)
    closing.set()
    if trace:
        import jax
        jax.profiler.stop_trace()
    await engine.shutdown(drain=False)
    await asyncio.gather(*tasks, return_exceptions=True)
    return arrivals, tokens_of, failed, late


def run(bench, cell, config, family, mix, seed, seconds, trace, t_proc0,
        device, peaks, break_with=None, trace_dir=None, limits=None):
    """One run of a serving cell. ``break_with`` (tests only) takes the
    sampler and returns the one handed to the scheduler in its place;
    ``limits`` (tests only) stands in for limits/<cell>.json."""
    from jax.profiler import TraceAnnotation
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.jit.api import ensure_compilation_cache

    watch = common.BuildWatch()
    ensure_compilation_cache()
    reqs = traffic.serve_requests(mix, seed, config["vocab_size"])
    rec = Recorder(config.get("sliding_window"))
    sampler = break_with(rec.sampler) if break_with else rec.sampler
    t_b = time.perf_counter()
    model, adapter, sched = _build(family, config, seed, sampler)
    common.note(phase="built", since_start_s=time.perf_counter() - t_proc0,
                build_s=time.perf_counter() - t_b, builds=len(watch.builds))
    pool_total = sum(c.num_pages for c in adapter.caches)

    inner_step = sched.step

    def step():
        with TraceAnnotation("bench.sched_step"):
            ev = inner_step()
        rec.step_end(ev, pool_total - sum(c.num_free_pages
                                          for c in adapter.caches))
        return ev

    sched.step = step
    notes = []

    async def main():
        async with ServingEngine(sched) as engine:
            return await _load(engine, reqs, mix, rec, seconds, trace,
                               trace_dir, notes)

    arrivals, gen_tokens, failed, late = asyncio.run(main())
    if rec.t1 is None:
        raise SystemExit("benchmark: the window never closed")
    setup_s = rec.t0 - t_proc0
    window_s = rec.t1 - rec.t0
    peak = common.memory_peak_bytes()
    in_win = lambda s: rec.first_step <= s <= rec.last_step    # noqa: E731
    wsteps = [s for i, s in enumerate(rec.steps) if in_win(i)]

    # tokens that reached a client from the window's steps, and their gaps
    tokens, gaps = 0, []
    for rid, steps in rec.gen_step.items():
        times = arrivals.get(rid, [])
        for k, s in enumerate(steps[:len(times)]):
            if in_win(s):
                tokens += 1
                if k > 0:
                    gaps.append(1e3 * (times[k] - times[k - 1]))
    attempted = sum(1 for q in reqs if q["id"] in arrivals)
    builds = watch.count_between(rec.t0_ns, rec.t1_ns)
    counters = {
        "steps": len(wsteps), "tokens_delivered": tokens,
        "gaps": len(gaps),
        "tokens_fed": sum(s["tokens_fed"] for s in wsteps),
        "rows_sampled": sum(s["rows_sampled"] for s in wsteps),
        "ctx_fed": sum(s["ctx_fed"] for s in wsteps),
        "ctx_rows": sum(s["ctx_rows"] for s in wsteps),
        "builds_in_window": builds,
        "pool_pages": pool_total,
        "pool_pages_used_peak": max(s["pool_used"] for s in wsteps),
        "generator_late_s_max": max(late) if late else 0.0,
    }
    # each step's packed count against the bucket the scheduler pads it to
    counters["packed_tokens"] = sum(s["prefill"] + s["decode"]
                                    for s in wsteps)
    from paddle_tpu.inference.serving import bucket_packed_tokens
    counters["padded_tokens"] = sum(
        bucket_packed_tokens(s["prefill"] + s["decode"],
                             sched.serving_buckets)
        - (s["prefill"] + s["decode"])
        for s in wsteps if s["prefill"] + s["decode"] > 0)
    e2e = {"serve_tokens_per_s": tokens / window_s,
           "tpot_p95_ms": common.quantile(gaps, 0.95) if gaps else None,
           "setup_s": setup_s}
    common.note(phase="window", steps=len(wsteps), window_s=window_s,
                tokens=tokens, gaps=len(gaps),
                tpot_p50_ms=common.quantile(gaps, 0.5) if gaps else None,
                builds_in_window=builds, setup_s=setup_s,
                warmup_steps=rec.first_step, notes=notes[:5])

    # free the program, then the reference over a sample of the requests
    by_id = {q["id"]: q for q in reqs}
    served = {rid: (by_id[rid], len(arrivals.get(rid, ())))
              for rid in rec.gen_step}
    top_logit = rec.top_logit
    del model, adapter, sched, inner_step, step
    gc.collect()
    compared, info = check(config, family, mix, seed, served, gen_tokens,
                           top_logit,
                           limits or common.load_limits(cell["name"]))
    common.note(phase="check", **info)
    ok = all(v["ok"] for v in compared.values()) and not failed
    return {"e2e": e2e, "counters": counters, "window_s": window_s,
            "window_ns": (rec.t0_ns, rec.t1_ns), "peak": peak,
            "compared": compared, "correct": ok, "attempted": attempted,
            "failed": len(failed), "build_spans": watch.spans(),
            "sync_ns": rec.sync_ns}


def pick_sample(served, gen_tokens, seed, k):
    """Requests compared with the reference: the longest served sequence
    and k - 1 more drawn from the seed."""
    ids = sorted(r for r in served if served[r][1] > 0 and r in gen_tokens)
    if not ids:
        return []
    length = lambda r: len(served[r][0]["prompt"]) + served[r][1]  # noqa
    longest = max(ids, key=length)
    rest = [r for r in ids if r != longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 5])
    rng.shuffle(rest)
    return [longest] + rest[:max(0, k - 1)]


def check(config, family, mix, seed, served, gen_tokens, top_logit, limits,
          control=False):
    """Run the family's reference once over each sampled prompt with its
    served tokens. With ``control`` the lower-precision reference is read
    at the same positions instead of the served tokens."""
    sample = pick_sample(served, gen_tokens, seed, int(mix["check_sample"]))
    if not sample:
        # nothing was served, so nothing compares: not correct (1e30 and
        # not infinity, which JSON cannot carry)
        return ({"served_gap": {"value": 1e30,
                                "limit": limits["served_gap"], "ok": False}},
                {"tokens_compared": 0})
    seqs = []
    for rid in sample:
        q, n = served[rid]
        seqs.append((rid, q["prompt"], gen_tokens[rid][:n]))
    s_pad = -(-max(len(p) + len(g) for _, p, g in seqs) // 128) * 128
    ids = np.zeros((len(seqs), s_pad), np.int32)
    for r, (_, p, g) in enumerate(seqs):
        ids[r, :len(p) + len(g)] = p + g
    gather = np.zeros((len(seqs), s_pad, 1), np.int32)
    gather[:, :-1, 0] = ids[:, 1:]
    if control:
        _, arg, _ = family.serve_logits(config, seed, ids, gather,
                                        mode="int8")
        gather[:, :, 0] = arg
    best, _, got = family.serve_logits(config, seed, ids, gather)
    at, errs = [], []
    for r, (rid, p, g) in enumerate(seqs):
        for j, tok in enumerate(g):
            s = len(p) + j - 1               # logits at s predict token s+1
            at.append((r, s, tok))
            if not control and top_logit.get(rid) and \
                    top_logit[rid][j] is not None:
                errs.append(abs(top_logit[rid][j] - float(got[r, s, 0])))
    nums, info = correct.serving_numbers(at, best, got[:, :, 0])
    if errs:
        nums["logit_err"] = max(errs)
    info.update(sample=sample, padded_to=s_pad,
                numbers={k: float(v) for k, v in nums.items()})
    return correct.compare_serving(nums, limits), info
