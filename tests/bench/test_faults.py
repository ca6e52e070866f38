"""A run with the timed path broken underneath must come out not correct:
a token altered where the decode step or the packed prefill produces it,
and a decode step that returns its cache unchanged."""

import pytest

from tiny import make_root, run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))


def _altered(fn):
    def wrapped(*args, **kwargs):
        logits, cache = fn(*args, **kwargs)
        return logits.at[:, 7].add(1e4), cache
    return wrapped


def _stale(fn):
    def wrapped(self, params, cache, tokens):
        logits, _ = fn(self, params, cache, tokens)
        return logits, cache
    return wrapped


@pytest.mark.parametrize("fault", ["decode_token", "prefill_token", "stale_cache"])
def test_fault_is_not_correct(root, fault, monkeypatch):
    from repro.models.transformer import DecoderLM

    if fault == "decode_token":
        monkeypatch.setattr(DecoderLM, "decode_step", _altered(DecoderLM.decode_step))
    elif fault == "prefill_token":
        monkeypatch.setattr(DecoderLM, "prefill_packed", _altered(DecoderLM.prefill_packed))
    else:
        monkeypatch.setattr(DecoderLM, "decode_step", _stale(DecoderLM.decode_step))
    r = run(root, "tiny.chat", 21)
    assert not r["correct"], r["check"]
    assert r["check"]["max_logit_gap"]["value"] > r["check"]["max_logit_gap"]["limit"]
