"""Unified speculative decoding (ISSUE 19): verify rows ride the
ragged kernel.

The acceptance matrix: a scheduler given a draft model packs each
spec-active sequence's draft-k verify window as ONE right-aligned
(k+1)-token row of the ordinary ``prefill_chunk`` ragged step (per-
position logits out of the epilogue) and must be GREEDY-IDENTICAL to
the non-speculative scheduler — with no new per-k attend program
family. What composes with it is pinned too: spec × prefix-cache × kv
{float32, int8} verify-rollback under the strict page sanitizer
(COW/shared pages survive ``truncate``, zero leaks), and spec ×
host-swap preemption (draft KV discarded at swap-out, re-prefilled
from the committed prefix at swap-in) under a forced preemption storm;
adapters that cannot carry a verify row are refused at construction.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn.fault_injection import FaultInjector
from paddle_tpu.inference import (
    BatchScheduler,
    PagedLlamaAdapter,
    Request,
)
from paddle_tpu.models import LlamaForCausalLM, llama_tiny

PAGE = 4

_slow = pytest.mark.slow


def _tiny_cfg(**kw):
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("num_hidden_layers", 2)
    kw.setdefault("num_attention_heads", 2)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("max_position_embeddings", 128)
    return llama_tiny(**kw)


@pytest.fixture(scope="module")
def target():
    paddle.seed(0)
    return LlamaForCausalLM(_tiny_cfg())


@pytest.fixture(scope="module")
def draft():
    # a DIFFERENT model: proposals genuinely get rejected, so every
    # identity run exercises the verify-rollback truncate path
    paddle.seed(1)
    return LlamaForCausalLM(_tiny_cfg(num_hidden_layers=1))


_RNG = np.random.RandomState(0)
SHARED = _RNG.randint(1, 500, 10).tolist()
PROMPTS = {
    "a": SHARED + _RNG.randint(1, 500, 5).tolist(),
    "b": SHARED + _RNG.randint(1, 500, 3).tolist(),
    "c": _RNG.randint(1, 500, 7).tolist(),
}
N_NEW = {"a": 6, "b": 5, "c": 4}


def _serve(target, draft=None, kv=None, prefix=False,
           sanitizer=None, waves=None, faults=None, preempt=False,
           draft_k=3, buckets=None, max_new=None):
    """Run the standard workload; returns (generated, sched,
    adapter). ``waves`` submits request groups sequentially so later
    waves can hit the prefix cache of retired earlier ones."""
    adapter = PagedLlamaAdapter(target, num_pages=96, page_size=PAGE,
                                max_length=128, kv_cache_dtype=kv,
                                sanitizer=sanitizer)
    kw = {}
    if draft is not None:
        kw = dict(
            draft_model=PagedLlamaAdapter(
                draft, num_pages=96, page_size=PAGE, max_length=128,
                sanitizer=sanitizer),
            draft_k=draft_k)
    if preempt:
        kw.update(preempt=True, swap_bytes=1 << 22)
    fi = FaultInjector(faults) if faults else None
    sched = BatchScheduler(
        adapter, max_batch_size=4, prefix_cache=prefix,
        chunked_prefill=True, prefill_chunk_tokens=8,
        serving_buckets=buckets, fault_injector=fi, **kw)
    out = {}
    for wave in (waves or [list(PROMPTS)]):
        for rid in wave:
            sched.submit(Request(rid, list(PROMPTS[rid]),
                                 max_new_tokens=max_new
                                 if max_new is not None
                                 else N_NEW[rid]))
        done = sched.run_until_complete(max_steps=500)
        for k, v in done.items():
            out[k] = v.generated_ids
    stats = sched.page_pool_stats()
    if not prefix:  # the radix tree deliberately retains pages
        assert stats["free_pages"] == stats["total_pages"], stats
    return out, sched, adapter


class TestUnifiedSpecIdentity:
    def test_ragged_identical_to_nonspec(self, target, draft):
        base, s_base, _ = _serve(target)
        rag, s_rag, _ = _serve(target, draft)
        assert rag == base
        assert s_base.draft is None and s_rag.draft is not None
        st = s_rag.spec_stats
        assert st["rounds"] > 0
        assert s_base.spec_stats["rounds"] == 0
        # a different draft: proposals get rejected and rolled back
        assert st["accepted_draft_tokens"] < st["proposed_tokens"]
        # strictly better than one token per target call
        assert st["committed_tokens"] / st["target_calls"] > 1.0

    def test_full_acceptance_same_weights_draft(self, target):
        # draft == target: every proposal accepted, k+1 tokens per
        # round, still greedy-identical
        base, _, _ = _serve(target, max_new=9)
        got, s, _ = _serve(target, draft=target,
                           max_new=9)
        assert got == base
        st = s.spec_stats
        assert st["accepted_draft_tokens"] == st["proposed_tokens"]
        # each stream's first token comes off the prefill epilogue;
        # every remaining token lands in a full-acceptance window
        assert st["committed_tokens"] == len(PROMPTS) * (9 - 1)
        assert s._statusz_info()["spec"]["accept_rate"] == 1.0

    def test_no_new_attend_program_family(self, target, draft):
        # verify rows reuse the existing buckets: the kernel-shape
        # families and the bucket-bounded compile count of the ragged
        # target program match the non-spec chunked run
        buckets = (16, 32)
        _, _, ad0 = _serve(target, buckets=buckets)
        _, _, ad1 = _serve(target, draft,
                           buckets=buckets)
        kinds0 = sorted({k for k, *_ in ad0._kernel_shapes})
        kinds1 = sorted({k for k, *_ in ad1._kernel_shapes})
        assert kinds1 == kinds0
        # one dispatch shape per packed bucket, no per-k family
        assert ad1.compile_count <= len(buckets)
        assert set(ad0._dispatch_shapes) <= set(buckets)
        assert set(ad1._dispatch_shapes) <= set(buckets)

    def test_statusz_accept_rate_column(self, target, draft):
        _, s, _ = _serve(target, draft)
        info = s._statusz_info()
        spec = info["spec"]
        assert spec["mode"] == "ragged"
        assert spec["rounds"] == s.spec_stats["rounds"]
        assert 0.0 <= spec["accept_rate"] <= 1.0
        assert spec["tokens_per_round"] > 1.0

class _NoChunk:
    """An adapter that only speaks ``decode_token``."""

    def __init__(self, adapter):
        self.caches = adapter.caches
        self.alloc, self.free = adapter.alloc, adapter.free
        self.decode_token = adapter.decode_token


class _NoLogitsRows(_NoChunk):
    """...and one whose chunked step has no per-position epilogue."""

    def __init__(self, adapter):
        super().__init__(adapter)
        self._chunk = adapter.prefill_chunk

    def prefill_chunk(self, token_ids, seq_ids, start_positions=None,
                      pad_to=None):
        return self._chunk(token_ids, seq_ids, start_positions, pad_to)


class TestSpecNeedsTheChunkedStep:
    def _adapters(self, target, draft):
        return (PagedLlamaAdapter(target, num_pages=32, page_size=PAGE),
                PagedLlamaAdapter(draft, num_pages=32, page_size=PAGE))

    def test_draft_without_prefill_chunk_refused_by_name(self, target,
                                                         draft):
        ad, da = self._adapters(target, draft)
        with pytest.raises(ValueError,
                           match="draft adapter has no prefill_chunk"):
            BatchScheduler(ad, draft_model=_NoChunk(da))

    def test_target_without_logits_rows_refused_by_name(self, target,
                                                        draft):
        ad, da = self._adapters(target, draft)
        with pytest.raises(ValueError, match="takes no logits_rows="):
            BatchScheduler(_NoLogitsRows(ad), draft_model=da)
        with pytest.raises(ValueError,
                           match="target adapter has no prefill_chunk"):
            BatchScheduler(_NoChunk(ad), draft_model=da)
        with pytest.raises(ValueError, match="chunked_prefill=False"):
            BatchScheduler(ad, draft_model=da, chunked_prefill=False)
        # without a draft the same adapters serve as before
        assert not BatchScheduler(_NoChunk(ad)).chunked_prefill
        assert BatchScheduler(_NoLogitsRows(ad)).chunked_prefill

    def test_prefix_cache_and_preemption_always_available(self, target,
                                                          draft):
        ad, da = self._adapters(target, draft)
        s = BatchScheduler(ad, draft_model=da, prefix_cache=True,
                           preempt=True, swap_bytes=1 << 20)
        assert s.prefix_cache is not None
        assert s.swap_space is not None and s._preempt_enabled
        assert s._statusz_info()["spec"]["mode"] == "ragged"
        with pytest.raises(TypeError, match="spec_decode"):
            BatchScheduler(ad, draft_model=da, spec_decode="legacy")


class TestSpecPrefixKvRollback:
    """ISSUE-19 satellite: spec × prefix-cache × kv dtype rollback —
    COW/shared pages must survive the verify-rollback ``truncate``
    under the strict page sanitizer, with zero leaks after the tree
    drains."""

    @pytest.mark.parametrize("kv", [None, "int8"])
    def test_rollback_over_shared_prefix_pages(self, target, draft,
                                               kv):
        waves = [["a"], ["b"], ["c"]]  # b hits a's cached prefix
        base, _, _ = _serve(target, kv=kv, waves=waves)
        got, s, ad = _serve(target, draft, kv=kv,
                            prefix=True, sanitizer="strict",
                            waves=waves)
        assert got == base
        assert s.prefix_stats["hit_tokens"] > 0
        # the draft pool was refilled (never prefix-attached)
        assert s.spec_stats["refill_tokens"] > 0
        san = s.page_pool_stats()["sanitizer"]
        assert san["mode"] == "strict"
        assert san["violations"] == 0
        assert san["events"] > 0
        # drain the radix tree: every page must come home
        s.prefix_cache.evict(10 ** 6)
        stats = s.page_pool_stats()
        assert stats["free_pages"] == stats["total_pages"], stats


class TestSpecPreemptionStorm:
    """ISSUE-19 satellite: a spec-active victim swaps out with its
    draft KV discarded and resumes with the draft re-prefilled from the
    committed prefix (wait-free)."""

    def test_storm_identity_and_draft_refill(self, target, draft):
        base, _, _ = _serve(target)
        got, s, _ = _serve(target, draft,
                           sanitizer="strict", preempt=True,
                           faults="preempt_storm@6:2")
        assert got == base
        assert s.spec_stats["draft_discards"] > 0
        assert s.spec_stats["refill_tokens"] > 0
        san = s.page_pool_stats()["sanitizer"]
        assert san["violations"] == 0
        # the storm genuinely fired and fully unwound
        assert s._faults.counts["preempt_storm"] > 0
        assert s._swapped == {}
