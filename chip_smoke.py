#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at
Mistral-7B widths (hidden 4096, ffn 14336, 32/8 heads, head_dim 128,
vocab 32000; depth cut, weights random from a fixed seed):

* serve: ``LlamaForCausalLM`` -> ``PagedLlamaAdapter`` -> ``BatchScheduler``
  with its shipped defaults (chunked prefill, unified ragged kernel, fused
  step, buckets), then the same scheduler behind ``ServingEngine``;
* train: a ``@paddle.jit.to_static`` step (forward, backward, AdamW), 4 steps;
* ``--chips 4``: ONLY the tensor-parallel path (fleet mp=4 decoder block under
  ``to_static``) and the one-device run it is compared with.

One process, no child that needs the chip. It fails unless JAX's first
device is a TPU, never sets a platform, never falls back, and a phase that
fails ends the run at once (non-zero exit, no result line). Earlier stdout
lines are JSON notes, one per phase — notes, not metrics. The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

``--tiny`` only shrinks sizes so the control flow can be rehearsed on the
CPU backend (Pallas in interpret mode); it is refused on a TPU.
"""
import argparse
import asyncio
import gc
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# sizes: the real run and its CPU rehearsal differ ONLY here
# ---------------------------------------------------------------------------
FULL = dict(
    cfg={},                       # mistral_7b() widths as published
    # serve depth: 8 of 32 layers = 3.5 GB of bf16 layers + 0.5 GB embed/head;
    # the model is built in fp32 on the device before the bf16 cast (8 GB
    # transient), the pool adds 1 GiB (2048 pages x 16 tok x 8 layers) and
    # the train phase follows in the same process — 8 leaves room for all.
    serve_layers=8, num_pages=2048, n_requests=8,
    serve_why="8 of 32 layers: 4.0 GB of bf16 weights, built in fp32 on the "
              "device first (8 GB transient), + 1 GiB pool, and the train "
              "phase follows in the same 16 GB",
    prompt_len=(200, 1500), new_tokens=(32, 64), dense_pad=512,
    kernel_check=dict(b=4, t=16, lens=(5000, 4100, 1500, 16), window=4096),
    # decode rows at as many KV heads as heads (group 1): the kernel's
    # few-row form, the pages multiplied in the pool's own layout
    kernel_check_mha=dict(b=4, t=1, lens=(2100, 1040, 300, 16), window=0),
    # train depth: AdamW multi-precision keeps 14 B/param (bf16 param, fp32
    # master, m, v) + 2 B/param of grads; embed + head alone are 262 M
    # params (4.2 GB). 2 layers = 698 M params = 11.2 GB, the most 16 GB holds
    # next to the activations of one 2048-token sequence.
    train_layers=2, train_seq=2048, train_steps=4,
    train_why="2 of 32 layers: AdamW multi-precision is 16 B/param with "
              "grads and embed+head alone are 262 M params; 2 layers = 698 M "
              "params = 11.2 GB, 3 would need 14.4 GB before activations",
    tp_seq=2048,
)
TINY = dict(
    cfg=dict(vocab_size=512, hidden_size=512, intermediate_size=1024,
             num_attention_heads=4, num_key_value_heads=2,
             sliding_window=64),
    serve_layers=2, num_pages=128, n_requests=4,
    prompt_len=(20, 90), new_tokens=(4, 8), dense_pad=128,
    kernel_check=dict(b=2, t=8, lens=(100, 24), window=64),
    kernel_check_mha=dict(b=2, t=1, lens=(100, 24), window=0),
    train_layers=1, train_seq=128, train_steps=4,
    serve_why="rehearsal", train_why="rehearsal",
    tp_seq=64,
)
SEED = 0
# bf16 tolerances, stated where they are used
KERNEL_TOL = 2e-2     # |ragged kernel - f32 reference| / max|reference|
GREEDY_TOL = 0.125    # dense logit margin (4 bf16 ulps at |logit| in [4, 8))
TP_TOL = 3e-2         # |mp4 - one device| / max|one device|


def note(phase, **kw):
    print(json.dumps({"phase": phase, **kw}), flush=True)


class CompileWatch:
    """Counts XLA program builds and persistent-cache traffic through
    jax.monitoring (a build served from the persistent cache counts as a
    build AND a hit)."""

    def __init__(self):
        mon = jax.monitoring
        self.builds = self.build_s = self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._ev)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.builds += 1
            self.build_s += secs

    def _ev(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self):
        return dict(builds=self.builds, build_s=round(self.build_s, 2),
                    cache_hits=self.hits, cache_misses=self.misses)


def device_bytes(key="peak_bytes_in_use"):
    return (jax.devices()[0].memory_stats() or {}).get(key)


def require(cond, msg):
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def no_fallback(stats, what):
    bad = {k: v for k, v in stats.items() if k.endswith(":xla_fallback")}
    require(not bad, f"{what}: kernels fell to the XLA reference: {bad}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def check_kernel_vs_reference(S, cfg, check="kernel_check", kvh=None):
    """One paged_ragged_attention call at these widths against the dense
    f32 reference, on this device (sequence lengths past the window).
    ``kvh``: KV heads other than the model's."""
    from paddle_tpu.ops.kernels import (
        paged_ragged_attention, paged_ragged_attention_reference)

    kc = S[check]
    h, kvh, d, page = (cfg.num_attention_heads,
                       kvh or cfg.num_key_value_heads, cfg.head_dim, 16)
    b, t, lens = kc["b"], kc["t"], list(kc["lens"])
    max_pages = -(-max(lens) // page)
    npages = b * max_pages
    rng = np.random.RandomState(SEED)
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.bfloat16)
    kp = jnp.asarray(rng.randn(npages, page, kvh, d), jnp.bfloat16)
    vp = jnp.asarray(rng.randn(npages, page, kvh, d), jnp.bfloat16)
    tbl = jnp.asarray(rng.permutation(npages).reshape(b, max_pages),
                      jnp.int32)
    seq_lens = jnp.asarray(lens, jnp.int32)
    q_lens = jnp.asarray([min(t, n) for n in lens], jnp.int32)
    got = np.asarray(paged_ragged_attention(
        q, kp, vp, tbl, seq_lens, q_lens=q_lens, window=kc["window"]),
        np.float32)
    want = paged_ragged_attention_reference(
        q, kp, vp, tbl, seq_lens, q_lens=q_lens, window=kc["window"])
    require(np.isfinite(got).all(), "ragged kernel output not finite")
    err = float(np.abs(got - want).max() / np.abs(want).max())
    require(err <= KERNEL_TOL,
            f"ragged kernel vs reference: rel err {err} > {KERNEL_TOL}")
    return err


def check_greedy_vs_dense(S, model, req):
    """Teacher-forced dense forward (flash kernel, same weights, this
    device) over prompt + served tokens: at every generated position the
    served token must be the dense argmax, or a near-tie of it — its dense
    logit within GREEDY_TOL of the dense top (random bf16 weights put ~1 in
    6 top-2 gaps under that, and the two paths round differently)."""
    ids = list(req.prompt_ids) + list(req.generated_ids[:-1])
    n0, n = len(req.prompt_ids), len(req.generated_ids)
    pad = -(-len(ids) // S["dense_pad"]) * S["dense_pad"]
    x = np.zeros((1, pad), "int64")
    x[0, :len(ids)] = ids
    import paddle_tpu as paddle

    with paddle.no_grad():
        logits = model(paddle.to_tensor(x))
    rows = np.asarray(logits._data[0, n0 - 1:n0 - 1 + n], np.float32)
    require(np.isfinite(rows).all(), f"{req.req_id}: dense logits not finite")
    served = np.asarray(req.generated_ids)
    exact = int((rows.argmax(-1) == served).sum())
    margin = rows.max(-1) - rows[np.arange(n), served]
    require(float(margin.max()) <= GREEDY_TOL,
            f"{req.req_id}: served token is not the dense greedy token: "
            f"dense margin {float(margin.max())} > {GREEDY_TOL} "
            f"({exact}/{n} exact)")
    require(exact >= 0.7 * n,
            f"{req.req_id}: only {exact}/{n} tokens equal the dense argmax")
    return dict(req=req.req_id, exact=exact, of=n,
                max_margin=round(float(margin.max()), 4))


def phase_serve(S, watch):
    import paddle_tpu as paddle
    from paddle_tpu.inference import BatchScheduler, Request, ServingEngine
    from paddle_tpu.inference.paged_llama import PagedLlamaAdapter
    from paddle_tpu.models import LlamaForCausalLM, mistral_7b
    from paddle_tpu.ops.kernels import kernel_dispatch_stats

    kernel_dispatch_stats(reset=True)
    t_build = time.perf_counter()
    paddle.seed(SEED)
    cfg = mistral_7b(num_hidden_layers=S["serve_layers"], **S["cfg"])
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    model.eval()
    adapter = PagedLlamaAdapter(model, num_pages=S["num_pages"], page_size=16)
    sched = BatchScheduler(adapter)
    t_build = time.perf_counter() - t_build

    rng = np.random.RandomState(SEED)
    reqs = [Request(f"r{i}",
                    rng.randint(1, cfg.vocab_size,
                                rng.randint(*S["prompt_len"])).tolist(),
                    max_new_tokens=int(rng.randint(*S["new_tokens"])))
            for i in range(S["n_requests"])]
    # arrivals spread over steps, so prefill chunks and decode rows of
    # earlier requests share packed steps
    first_call_s = steady_s = 0.0
    steps = mixed = building_steps = 0
    pending = list(reqs)

    def step():
        nonlocal first_call_s, steady_s, steps, mixed, building_steps
        b0, t0 = watch.builds, time.perf_counter()
        ev = sched.step()
        dt = time.perf_counter() - t0
        if watch.builds > b0:
            first_call_s += dt
            building_steps += 1
        else:
            steady_s += dt
        steps += 1
        mixed += bool(ev.get("prefill_tokens") and ev.get("decode_tokens"))

    per_wave = max(1, len(reqs) // 3)
    while pending or sched.num_active or sched.num_queued:
        for r in pending[:per_wave]:
            sched.submit(r)
        pending = pending[per_wave:]
        for _ in range(6 if pending else 1):
            step()
        require(steps < 20000, "scheduler did not drain")
    sched.run_until_complete()

    for r in reqs:
        require(r.finished and len(r.generated_ids) == r.max_new_tokens,
                f"{r.req_id}: state {r.state}, {len(r.generated_ids)} of "
                f"{r.max_new_tokens} tokens")
    require(mixed > 0, "no step packed prefill chunks with decode rows")
    require(sched.chunked_prefill, "chunked prefill is not on by default")
    kinds = adapter.attend_kinds_by_bucket
    require(all(k == ["ragged_fused"] for k in kinds.values()),
            f"a bucket left the fused unified ragged step: {kinds}")

    # the same scheduler behind the asyncio front end, two requests
    async def through_engine():
        ereqs = [Request(f"e{i}", list(reqs[i].prompt_ids),
                         max_new_tokens=reqs[i].max_new_tokens)
                 for i in (0, 1)]
        async with ServingEngine(sched) as eng:
            streams = [await eng.submit(r) for r in ereqs]
            toks = await asyncio.gather(*(s.tokens() for s in streams))
        for r, tk in zip(ereqs, toks):
            require(r.finished and tk == r.generated_ids
                    and len(tk) == r.max_new_tokens,
                    f"engine {r.req_id}: state {r.state}, streamed "
                    f"{len(tk)} of {r.max_new_tokens}")
        return ereqs

    t_eng = time.perf_counter()
    ereqs = asyncio.run(through_engine())
    t_eng = time.perf_counter() - t_eng

    served_stats = kernel_dispatch_stats(reset=True)
    no_fallback(served_stats, "serve")
    t_chk = time.perf_counter()
    dense = [check_greedy_vs_dense(S, model, r)
             for r in (reqs[0], reqs[1], ereqs[0], ereqs[1])]
    kernel_err = check_kernel_vs_reference(S, cfg)
    few_row_err = check_kernel_vs_reference(
        S, cfg, "kernel_check_mha", cfg.num_attention_heads)
    t_chk = time.perf_counter() - t_chk
    dense_stats = kernel_dispatch_stats(reset=True)
    no_fallback(dense_stats, "dense reference")
    require(dense_stats.get("flash_fwd:pallas", 0) > 0
            and served_stats.get("rms_norm:pallas", 0) > 0,
            f"flash/rms kernels not dispatched: {served_stats} {dense_stats}")

    note("serve", layers=cfg.num_hidden_layers, depth_why=S["serve_why"],
         hidden=cfg.hidden_size,
         ffn=cfg.intermediate_size, heads=[cfg.num_attention_heads,
                                           cfg.num_key_value_heads],
         vocab=cfg.vocab_size, window=cfg.sliding_window,
         pool_pages=S["num_pages"], page_size=16,
         requests=len(reqs),
         prompt_tokens=sum(len(r.prompt_ids) for r in reqs),
         tokens_generated=sum(len(r.generated_ids) for r in reqs + ereqs),
         steps=steps, mixed_steps=mixed, steps_that_built=building_steps,
         buckets=sorted(kinds),
         attend_programs=adapter.attend_program_count,
         wall_s=dict(build=round(t_build, 2),
                     first_call=round(first_call_s, 2),
                     steady=round(steady_s, 2), engine=round(t_eng, 2),
                     checks=round(t_chk, 2)),
         greedy_vs_dense=dense, greedy_tol=GREEDY_TOL,
         kernel_vs_reference_rel_err=round(kernel_err, 5),
         kernel_few_row_rel_err=round(few_row_err, 5),
         kernel_tol=KERNEL_TOL, dispatch=served_stats,
         dispatch_dense=dense_stats, peak_bytes_in_use=device_bytes(),
         compile=watch.snap())


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def phase_train(S, watch):
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as optim
    from paddle_tpu.models import LlamaForCausalLM, mistral_7b
    from paddle_tpu.ops.kernels import kernel_dispatch_stats

    kernel_dispatch_stats(reset=True)
    in_use_before = device_bytes("bytes_in_use")
    seq = S["train_seq"]
    paddle.seed(SEED)
    cfg = mistral_7b(num_hidden_layers=S["train_layers"], **S["cfg"])
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    opt = optim.AdamW(3e-4, parameters=model.parameters())
    opt._create_accumulators()

    @paddle.jit.to_static
    def train_step(x, y):
        _, loss = model(x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.RandomState(SEED)
    ids = rng.randint(0, cfg.vocab_size, (2, 1, seq))
    x = paddle.to_tensor(ids[0].astype("int32"))
    y = paddle.to_tensor(ids[1].astype("int64"))
    losses, walls, builds = [], [], []
    for _ in range(S["train_steps"]):
        b0, t0 = watch.builds, time.perf_counter()
        losses.append(float(np.asarray(train_step(x, y)._data)))
        walls.append(round(time.perf_counter() - t0, 3))
        builds.append(watch.builds - b0)

    stats = kernel_dispatch_stats(reset=True)
    require(all(np.isfinite(losses)), f"train losses not finite: {losses}")
    require(all(b < a for a, b in zip(losses, losses[1:])),
            f"loss does not fall on a repeated batch: {losses}")
    require(not any(builds[1:]),
            f"steps after the first built programs: {builds}")
    no_fallback(stats, "train")
    for k in ("flash_fwd", "flash_bwd", "rms_norm"):
        require(stats.get(f"{k}:pallas", 0) > 0,
                f"train: {k} not counted as pallas: {stats}")
    note("train", layers=cfg.num_hidden_layers, depth_why=S["train_why"],
         params=cfg.num_params(),
         seq=seq, batch=1, steps=len(losses), losses=losses,
         wall_s=dict(first_call=walls[0], steady=walls[1:]),
         builds_per_step=builds, dispatch=stats,
         bytes_in_use_before=in_use_before,
         peak_bytes_in_use=device_bytes(), compile=watch.snap())


# ---------------------------------------------------------------------------
# --chips 4: tensor parallel vs one device
# ---------------------------------------------------------------------------
def phase_tp(S, watch):
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import mistral_7b
    from paddle_tpu.models.llama import LlamaDecoderLayer
    from paddle_tpu.ops.kernels import kernel_dispatch_stats

    devs = jax.devices()
    require(len(devs) >= 4, f"--chips 4 needs 4 devices, JAX has {len(devs)}")
    cfg = mistral_7b(num_hidden_layers=1, **S["cfg"])
    seq = S["tp_seq"]
    x_np = np.random.RandomState(SEED).randn(1, seq, cfg.hidden_size)
    x_np = (0.5 * x_np).astype("float32")

    def run():
        """One decoder block (Column/RowParallelLinear projections),
        forward + backward once under to_static."""
        paddle.seed(SEED)
        layer = LlamaDecoderLayer(cfg)
        layer.bfloat16()
        w = layer.mlp.down_proj.weight

        @paddle.jit.to_static
        def fwd_bwd(x):
            out = layer(x)
            (out.astype("float32") ** 2).mean().backward()
            return out

        x = paddle.to_tensor(x_np).astype("bfloat16")
        t0 = time.perf_counter()
        out = np.asarray(fwd_bwd(x)._data, np.float32)
        wall = time.perf_counter() - t0
        grad = np.asarray(w.grad._data, np.float32)
        entry, state, raws = fwd_bwd._prepare((x,), {})
        text = entry["jitted"].lower(
            [state[i]._data for i in entry["rw_idx"]],
            [state[i]._data for i in entry["ro_idx"]], raws,
        ).compile().as_text()
        return layer, out, grad, text, wall

    # what it is compared with: the same seeded weights on ONE device of
    # this process (no mesh yet: every array sits on jax.devices()[0])
    kernel_dispatch_stats(reset=True)
    _, ref_out, ref_grad, _, ref_wall = run()
    gc.collect()

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 4,
                               "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    layer, out, grad, text, wall = run()
    stats = kernel_dispatch_stats(reset=True)
    no_fallback(stats, "tensor parallel")

    require(np.isfinite(out).all() and np.isfinite(grad).all(),
            "mp=4 output or gradient not finite")
    err_out = float(np.abs(out - ref_out).max() / np.abs(ref_out).max())
    err_grad = float(np.abs(grad - ref_grad).max() / np.abs(ref_grad).max())
    require(err_out <= TP_TOL and err_grad <= TP_TOL,
            f"mp=4 vs one device: out rel err {err_out}, down_proj grad "
            f"rel err {err_grad} > {TP_TOL}")
    # each of the 4 devices holds a quarter of every sharded weight
    spread = {}
    for name, p in layer.named_parameters():
        if not getattr(p, "is_distributed", False):
            continue
        shards = p._data.addressable_shards
        on = {s.device for s in shards}
        sizes = {int(np.prod(s.data.shape)) for s in shards}
        require(len(on) == 4 and sizes == {p._data.size // 4},
                f"{name}: shards on {len(on)} devices, sizes {sizes} of "
                f"{p._data.size}")
        spread[name] = [str(s.data.shape) for s in shards][0]
    require(len(spread) >= 7, f"sharded weights found: {sorted(spread)}")
    colls = [c for c in ("collective-permute", "all-reduce", "all-gather",
                         "reduce-scatter") if c in text]
    require("collective-permute" in colls or "all-reduce" in colls,
            f"compiled mp=4 program has no collective: {colls}")
    note("tensor_parallel", mp=4, seq=seq, hidden=cfg.hidden_size,
         ffn=cfg.intermediate_size, out_rel_err=round(err_out, 5),
         grad_rel_err=round(err_grad, 5), tol=TP_TOL,
         shard_shape_per_device=spread, collectives=colls,
         kernels_in_program=text.count("tpu_custom_call"),
         wall_s=dict(one_device_first_call=round(ref_wall, 2),
                     mp4_first_call=round(wall, 2)),
         dispatch=stats, peak_bytes_in_use=device_bytes(),
         compile=watch.snap())


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the tensor-parallel path and the "
                         "one-device run it is compared with")
    ap.add_argument("--tiny", action="store_true",
                    help="shrink sizes for a CPU rehearsal (refused on tpu)")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if args.tiny:
        if dev.platform == "tpu":
            raise SystemExit("chip_smoke: --tiny is a CPU rehearsal of the "
                             "control flow; it is refused on tpu")
    elif dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX's first device is "
            f"platform={dev.platform!r} kind={dev.device_kind!r}")

    import paddle_tpu as paddle
    from paddle_tpu import csrc
    from paddle_tpu.jit.api import ensure_compilation_cache

    S = dict(TINY if args.tiny else FULL)
    if args.tiny:
        # the rehearsal walks the same dispatch: Pallas in interpret mode
        paddle.set_flags({"FLAGS_pallas_interpret": True})
    watch = CompileWatch()
    ensure_compilation_cache()
    note("start", platform=dev.platform, device_kind=dev.device_kind,
         count=len(jax.devices()), jax=jax.__version__, tiny=args.tiny,
         native_runtime="built" if csrc.available() else "python fallback",
         compile_cache_dir=jax.config.jax_compilation_cache_dir)

    if args.chips == 4:
        phase_tp(S, watch)
    else:
        phase_serve(S, watch)
        gc.collect()
        phase_train(S, watch)
    snap = watch.snap()
    note("end", compile=snap, compile_cache_hit=snap["cache_hits"] > 0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
