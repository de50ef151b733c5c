"""The float32 reference against the served path, at the tiny size: a
chunked prefill, then decode steps through the KV cache of a full slot
batch, every layer on the way (attention, the EaaS MoE layer with router,
dispatch over the logical servers, grouped GEMM and combine; and, in the
DeepSeek-style variant, a leading dense FFN layer, sigmoid scores and a
shared expert) and the head over the vocabulary.

Both variants route to 4 of 16 experts, so a near-tie in routing can flip
an expert between the bf16 path and the float32 reference at a few
positions; the served path's bf16 rounding moves the logits elsewhere by
about 1% of their norm.  So the bound is on the positions' 90th
percentile."""

import numpy as np
import pytest

from bench import system, weights
from bench.models import mixtral
from bench_tiny import tiny_config

SEED = 2 ** 31 + 5
# bf16 weights and activations against float32: relative L2 of the logits
REL_TOL = 3e-2


def _variant(name: str) -> dict:
    cfg = tiny_config()
    if name == "dense_shared":
        cfg.update(first_k_dense_replace=1, n_shared_experts=1,
                   scoring_func="sigmoid", intermediate_size=256,
                   moe_intermediate_size=128)
    return cfg


@pytest.fixture(scope="module", params=["mixtral", "dense_shared"])
def served(request):
    cfg = _variant(request.param)
    a = mixtral.arch(cfg)
    cluster, eng, _, _ = system.build(cfg, a, SEED)
    return a, eng.executor


def _rel(x, y):
    return float(np.linalg.norm(x - y) / np.linalg.norm(y))


def _rel_rows(x, y):
    return np.linalg.norm(x - y, axis=-1) / np.linalg.norm(y, axis=-1)


def test_prefill_then_decode_match_the_reference(served):
    a, ex = served
    rng = np.random.default_rng(3)
    B = ex.max_batch
    prompts = [rng.integers(0, a.vocab, n).astype(np.int32)
               for n in (64, 96, 32, 64)]
    toks, errs = [], []
    for b, p in enumerate(prompts):
        for s in range(0, len(p), 32):
            lg, _ = ex.prefill_chunk(b, p[s:s + 32], s, is_first=s == 0,
                                     is_last=s + 32 >= len(p))
        toks.append([int(np.asarray(lg)[0, :a.vocab].argmax())])
        first = np.asarray(lg)[0, :a.vocab]
        ref = mixtral.forward(a, SEED, [p], [np.array([len(p) - 1])])[0][0]
        errs.append(_rel(first, ref))
    steps = 4
    got = [[] for _ in range(B)]
    for _ in range(steps):
        t = np.asarray([[tk[-1]] for tk in toks], np.int32)
        lg, load = ex.decode(t)
        lg = np.asarray(lg)[:, :a.vocab]
        assert int(np.asarray(load).sum()) == B * a.top_k * a.moe_layers
        for b in range(B):
            got[b].append(lg[b])
            toks[b].append(int(lg[b].argmax()))
    seqs = [np.concatenate([p, np.asarray(tk[:-1], np.int32)])
            for p, tk in zip(prompts, toks)]
    reads = [np.arange(len(p), len(p) + steps) for p in prompts]
    ref = mixtral.forward(a, SEED, seqs, reads)
    for b in range(B):
        errs.extend(_rel_rows(np.stack(got[b]), ref[b]))
    assert np.percentile(errs, 90) < REL_TOL


def test_weights_are_a_function_of_the_seed(served):
    a, ex = served
    key = weights.base_key(SEED)
    # the first MoE layer is the first of the stacked blocks
    lk = weights.layer_key(key, a.first_k_dense)
    e3 = weights.expert(lk, a, 3)["w_gate"]
    slots = np.asarray(ex.params["blocks"]["moe"]["servers"]["w_gate"][0])
    layout = weights.slot_experts(a.num_experts, slots.shape[0],
                                  ex.pool.redundant_table)
    held = [(s, j) for s, j in zip(*np.nonzero(layout == 3))]
    assert len(held) >= 1
    for s, j in held:
        np.testing.assert_array_equal(slots[s, j].astype(np.float32),
                                      np.asarray(e3, np.float32))
    # a seed past 32 bits is not folded onto a smaller one
    other = weights.expert(
        weights.layer_key(weights.base_key(5), a.first_k_dense), a, 3)
    assert not np.array_equal(np.asarray(other["w_gate"]), np.asarray(e3))


def test_lower_precision_moves_the_reference():
    a = mixtral.arch(tiny_config())
    rng = np.random.default_rng(4)
    seq = rng.integers(0, a.vocab, 40).astype(np.int32)
    reads = [np.arange(8, 40)]
    f32 = mixtral.forward(a, 9, [seq], reads)[0]
    again = mixtral.forward(a, 9, [seq], reads)[0]
    np.testing.assert_array_equal(f32, again)
    gaps = {q: _rel(mixtral.forward(a, 9, [seq], reads, quant=q)[0], f32)
            for q in ("int8", "fp8")}
    assert 0 < gaps["int8"] < gaps["fp8"]

