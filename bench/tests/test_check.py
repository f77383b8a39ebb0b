"""The comparison that decides ``correct``, driven through a whole run of
a tiny stand-in of each cell on the CPU (the look for a chip skipped):

* sound runs come out correct;
* the control (retrieval scored in bfloat16, tokens that an fp8 forward
  puts first) reads above the limits;
* a run whose served path is broken underneath comes out not correct:
  an answer token altered where it is produced, retrieved passages
  altered where they are produced, and QKV biases or norm scales lost
  from the served weights.

At this size the model's logits are smaller than at full width, so the
token limit is the tiny size's own (``TINY_TOKEN_LIMIT``); the cells'
limits are set from chip runs (``PERF.md``).
"""
import pytest

import tiny
import run as B

TINY_TOKEN_LIMIT = 0.03


def _run(name, seed, tamper=None, control=False):
    cell = tiny.cell(name)
    conf = tiny.conf(cell["config"])
    conf["limits"]["token_logit_gap"] = TINY_TOKEN_LIMIT
    mix = tiny.mix(cell["traffic"], check={"requests": 24, "tokens": 150})
    return B.run(name, seed, 4.0, False, cell=cell, conf=conf,
                 mix=mix, require_chip=False,
                 build_kw=tiny.build_kw(cell["config"]),
                 tamper=tamper, control=control)


@pytest.mark.parametrize("name", ["chatglm3-6b.rag_single",
                                  "mistral-7b-v0.3.rag_backlog"])
def test_sound_run_is_correct_and_control_is_not(name):
    res = _run(name, 2 ** 31 + 7, control=True)
    cmp = res["compared"]
    assert res["correct"], cmp
    assert res["failed"] == 0 and res["attempted"] > 0
    assert cmp["control.retrieval_gap"]["value"] > \
        cmp["retrieval_gap"]["limit"]
    assert cmp["control.token_logit_gap"]["value"] > TINY_TOKEN_LIMIT


def _alter_token(served):
    tok = served.generator.tok
    decode = tok.decode

    def altered(ids):
        ids = list(ids)
        ids[-1] = (int(ids[-1]) + 1) % tok.vocab_size
        return decode(ids)
    tok.decode = altered


def _alter_passages(served):
    store = served.store
    get_chunks = store.get_chunks

    def altered(ids):
        return get_chunks((ids + 1) % len(store.chunks))
    store.get_chunks = altered


def _lose(served, names):
    """Put ones (norm scales) or zeros (biases) back where the benchmark
    wrote its seeded values."""
    import jax.numpy as jnp
    ex = served.generator.exec
    for i, (kind, lp) in enumerate(ex.layers):
        lp = dict(lp, attn=dict(lp["attn"]))
        for part in (lp, lp["attn"]):
            for nm in names:
                if nm in part:
                    fill = jnp.ones_like if nm.startswith("norm") else \
                        jnp.zeros_like
                    part[nm] = fill(part[nm])
        ex.layers[i] = (kind, lp)


def _lose_biases(served):
    _lose(served, ("bq", "bk", "bv"))


def _lose_norms(served):
    _lose(served, ("norm1", "norm2"))


@pytest.mark.parametrize("tamper,number", [(_alter_token, "token_logit_gap"),
                                           (_alter_passages,
                                            "retrieval_gap"),
                                           (_lose_biases, "token_logit_gap"),
                                           (_lose_norms, "token_logit_gap")])
def test_broken_path_is_not_correct(tamper, number):
    res = _run("chatglm3-6b.rag_single", 12345, tamper=tamper)
    assert not res["correct"]
    cmp = res["compared"][number]
    assert cmp["value"] > cmp["limit"]
