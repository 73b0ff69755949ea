"""The served comparison's two devices against the plain way: the head taken
in blocks of rows gives the columns that whole logits give, and a request
padded to a rung of the ladder reads what it reads padded to `seq_len`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import common, serve
from lib.weights import flat, make_params
from reference import gpt2

MODEL = common.sized(common.load_json("configs", "gpt2m-serve.json"), "model", True)


@pytest.fixture(scope="module")
def params():
    return make_params(gpt2.param_shapes(MODEL), 2**31 + 3)


@pytest.mark.parametrize("lowp", [False, True])
def test_head_in_blocks_gives_the_columns_of_whole_logits(params, monkeypatch, lowp):
    t, v = 48, MODEL["vocab_size"]
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(0, v, size=t).astype(np.int32))
    picked = jnp.roll(tokens, -1)
    pflat = flat(params)
    with jax.default_matmul_precision("highest"):
        whole = gpt2.logits(pflat, tokens[None], MODEL, lowp)[0]
        feats = gpt2.features(pflat, tokens[None], MODEL, lowp)[0]
        # 200 rows a block: 512 = 2 x 200 + 112, so the last block steps back.
        monkeypatch.setattr(serve, "HEAD_BLOCK_LOGITS", 200 * t)
        best, at, of_picked = serve._head_columns(gpt2, pflat, feats, picked, MODEL, lowp)
    assert (np.asarray(at) == np.asarray(whole.argmax(-1))).all()
    np.testing.assert_allclose(best, whole.max(-1), rtol=0, atol=1e-6)
    np.testing.assert_allclose(
        of_picked, jnp.take_along_axis(whole, picked[:, None], -1)[:, 0], rtol=0, atol=1e-6)


def test_padding_to_a_rung_reads_what_padding_to_seq_len_reads(params, monkeypatch):
    rng = np.random.default_rng(11)
    # 128 positions: rungs 16, 32, 64, 128; one request on each
    sample = [(rng.integers(0, MODEL["vocab_size"], size=n).astype(np.int32), n // 2)
              for n in (12, 30, 64, 100)]
    half = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    on_rungs = [serve.served_gap(gpt2, MODEL, half, [one]) for one in sample]
    monkeypatch.setattr(serve, "PAD_LADDER", (1,))
    at_seq_len = [serve.served_gap(gpt2, MODEL, half, [one]) for one in sample]
    assert [n for _, n in on_rungs] == [6, 15, 32, 50] == [n for _, n in at_seq_len]
    assert all(g > 0 for g, _ in on_rungs)  # random tokens are not the reference's best
    np.testing.assert_allclose([g for g, _ in on_rungs], [g for g, _ in at_seq_len],
                               rtol=0, atol=1e-5)
    whole = serve.served_gap(gpt2, MODEL, half, sample)
    assert whole == (max(g for g, _ in on_rungs), 103)
