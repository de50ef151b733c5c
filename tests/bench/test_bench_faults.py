"""Each fault a serving cell can have, planted under a whole harness run
on the CPU (the look for a chip left out), turns ``correct`` false."""

import pytest

from bench_tiny import tiny_run


def _cache_unchanged(monkeypatch):
    from repro.serving.executor import Executor

    decode = Executor.decode

    def stale(self, tokens):
        old = self.cache
        out = decode(self, tokens)
        self.cache = old          # the step returns its state unchanged
        return out
    monkeypatch.setattr(Executor, "decode", stale)


def _token_altered(monkeypatch):
    from repro.serving import engine

    sample_batch = engine.sample_batch

    def altered(logits, *a):
        return (sample_batch(logits, *a) + 1) % 512
    monkeypatch.setattr(engine, "sample_batch", altered)


def _expert_exchange_dropped(monkeypatch):
    import jax.numpy as jnp

    from repro.core import comm

    monkeypatch.setattr(comm, "return_to_clients",
                        lambda out, axis, mode: jnp.zeros_like(out))


def _half_batch_left_out(monkeypatch):
    from repro.serving.executor import Executor

    decode = Executor.decode

    def half(self, tokens):
        logits, load = decode(self, tokens)
        B = logits.shape[0]
        # the second half of the slots gets the first half's logits
        return logits.at[B // 2:].set(logits[:B - B // 2]), load
    monkeypatch.setattr(Executor, "decode", half)


@pytest.mark.parametrize("plant", [_cache_unchanged, _token_altered,
                                   _expert_exchange_dropped,
                                   _half_batch_left_out],
                         ids=["cache_unchanged", "token_altered",
                              "expert_exchange_dropped",
                              "half_batch_left_out"])
def test_fault_is_caught(monkeypatch, plant):
    plant(monkeypatch)
    out = tiny_run("reason", seed=31)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checked"].values())
