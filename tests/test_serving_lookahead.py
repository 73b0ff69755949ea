"""One decode step of lookahead (ISSUE 34): the engine enqueues step n+1
before it fetches step n.

- a row that ends by eos while the next step is in flight loses exactly its
  extra token, its slot and blocks are taken again, and every request
  equals ``generate()``;
- with ``eos_id`` None every step but the first after an empty engine was
  enqueued ahead, no row is wasted, and the enqueueing of step n+1 comes
  before the fetch of step n;
- the drain rule: whatever reads or rewrites slot state outside ``step``
  lands the step in flight first, and every id still resolves exactly once.
"""

from __future__ import annotations

import pytest

pytestmark = pytest.mark.serving

import jax
import jax.numpy as jnp
import numpy as np

from _jit import jit_init

from frl_distributed_ml_scaffold_tpu.config.schema import (
    GPTConfig,
    PrecisionConfig,
)
from frl_distributed_ml_scaffold_tpu.models.generation import generate
from frl_distributed_ml_scaffold_tpu.models.gpt import GPT
from frl_distributed_ml_scaffold_tpu.precision import get_policy
from frl_distributed_ml_scaffold_tpu.serving import ServingEngine
from frl_distributed_ml_scaffold_tpu.telemetry import Tracer

FP32 = get_policy(PrecisionConfig(policy="fp32"))
TINY = dict(
    vocab_size=64, num_layers=2, num_heads=4, hidden_dim=64, seq_len=64,
    dropout=0.0,
)
CACHES = pytest.mark.parametrize(
    "cache_kw", [{"kv_block_size": 8}, {}], ids=["paged", "bucketed"]
)


@pytest.fixture(scope="module")
def gpt():
    model = GPT(GPTConfig(**TINY), FP32)
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, 64)
    return model, jit_init(model, tokens, train=False)["params"]


def _greedy(model, params, prompt, n_new, eos=None):
    """``generate()``'s greedy tokens, cut after the first eos."""
    out = np.asarray(generate(
        model, params, jnp.asarray(prompt)[None], max_new_tokens=n_new,
        temperature=0.0,
    ))[0]
    if eos is not None:
        hits = np.flatnonzero(out[prompt.size:] == eos)
        if hits.size:
            out = out[: prompt.size + int(hits[0]) + 1]
    return out


def _staggered(seed, n=7):
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(0, 64, size=int(rng.integers(2, 12))).astype(np.int32),
         int(rng.integers(5, 14)))
        for _ in range(n)
    ]


def _run_staggered(eng, work):
    """Submit two, then one more every other step: admissions land while a
    step is in flight. Returns {id: completion} and {id: (prompt, n_new)}."""
    ids, done, todo = {}, {}, list(work)
    for _ in range(2):
        p, n = todo.pop(0)
        ids[eng.submit(p, n)] = (p, n)
    steps = 0
    while eng.pending or todo:
        if todo and steps % 2 == 1:
            p, n = todo.pop(0)
            ids[eng.submit(p, n)] = (p, n)
        for c in eng.step():
            assert c.id not in done, f"request {c.id} resolved twice"
            done[c.id] = c
        steps += 1
    for c in eng.run():  # nothing pending: what a drain left behind
        assert c.id not in done
        done[c.id] = c
    return done, ids


@CACHES
def test_row_ended_by_eos_loses_only_its_extra_token(gpt, cache_kw):
    """A row whose token is eos has run one step too many by the time the
    host knows: that row of the step in flight is dropped (counted), its
    slot and blocks are taken again by the next admission, and every
    request still equals ``generate()`` cut at its eos."""
    model, params = gpt
    work = _staggered(34)
    # An eos that ends the first request early: its third greedy token.
    p0, n0 = work[0]
    eos = int(_greedy(model, params, p0, n0)[p0.size + 2])
    eng = ServingEngine(
        model, params, num_slots=2, temperature=0.0, eos_id=eos, **cache_kw
    )
    done, ids = _run_staggered(eng, work)
    assert sorted(done) == sorted(ids)
    by_eos = 0
    for rid, (p, n) in ids.items():
        want = _greedy(model, params, p, n, eos)
        np.testing.assert_array_equal(
            done[rid].tokens, want, err_msg=f"request {rid}")
        ended = want.size < p.size + n or want[-1] == eos
        assert done[rid].finish_reason == ("eos" if ended else "length")
        # Ended by eos with budget left: the step ahead held its row.
        by_eos += ended and want.size < p.size + n
    assert by_eos >= 1
    # Seven requests through two slots: the freed slots were taken again.
    assert eng.stats["admitted"] == len(work) > eng.num_slots
    # One wasted row for each request that eos ended with a step in flight
    # behind it; none for the rest.
    wasted = eng.telemetry.counter("serve_decode_wasted_rows_total").value
    assert 1 <= wasted == eng.stats["decode_wasted_rows"] <= by_eos
    if cache_kw:
        assert len(eng._free) == eng.pool_blocks - 1 or eng._prefix_cache
        assert eng._reserved_future == 0
    eng.close()


@CACHES
def test_every_step_but_the_first_is_enqueued_ahead(gpt, cache_kw, monkeypatch):
    """With ``eos_id`` None a row's death is a count, so nothing is
    wasted; every `decode` span but the first after an engine that stood
    empty says ``ahead == 1``; and the decode program of step n+1 is
    called before ``jax.device_get`` fetches step n."""
    model, params = gpt
    work = _staggered(43)
    tracer = Tracer(capacity=100_000)
    eng = ServingEngine(
        model, params, num_slots=3, temperature=0.0, tracer=tracer,
        **cache_kw,
    )
    order = []
    call, get = eng._call, jax.device_get

    def recording(program, key, fn, *args):
        out = call(program, key, fn, *args)
        if program in ("decode", "paged_decode"):
            order.append(("enqueue", out[1]))  # (kept: ids stay apart)
        return out

    def fetching(x):
        if isinstance(x, jax.Array) and x.shape == (eng.num_slots,):
            order.append(("fetch", x))
        return get(x)

    eng._call = recording
    monkeypatch.setattr(jax, "device_get", fetching)
    done, ids = _run_staggered(eng, work)
    monkeypatch.undo()
    assert sorted(done) == sorted(ids)
    for rid, (p, n) in ids.items():
        np.testing.assert_array_equal(
            done[rid].tokens, _greedy(model, params, p, n))
        assert len(done[rid].token_times_s) == n
        assert all(np.diff(done[rid].token_times_s) >= 0.0)

    decodes = [s for s in tracer.spans() if s["name"] == "decode"]
    steps = [s for s in tracer.spans() if s["name"] == "step"]
    assert len(decodes) == eng.stats["decode_steps"] > 10
    # The engine never stood empty inside this run: one step was not ahead.
    assert [s["ahead"] for s in decodes] == [0] + [1] * (len(decodes) - 1)
    assert eng.stats["decode_ahead"] == len(decodes) - 1
    assert eng.telemetry.counter(
        "serve_decode_ahead_total").value == len(decodes) - 1
    assert eng.telemetry.counter("serve_decode_wasted_rows_total").value == 0
    # A span describes the step it FETCHES: its `active` is the tokens its
    # own emit delivers.
    n_ticks = sum(n - 1 for _, n in ids.values())
    assert sum(s["active"] for s in decodes) == n_ticks
    assert len(steps) >= len(decodes)
    # Every fetch is of a step enqueued earlier, and between a step's
    # enqueueing and its fetch the NEXT step was enqueued (the last step
    # of the run has none ahead of it).
    enq = [i for i, (what, _) in enumerate(order) if what == "enqueue"]
    fetched = {id(x): i for i, (what, x) in enumerate(order) if what == "fetch"}
    assert len(fetched) == len(enq) == len(decodes)
    for k, i in enumerate(enq):
        at = fetched[id(order[i][1])]
        assert i < at
        if k + 1 < len(enq):
            assert enq[k + 1] < at, f"step {k + 1} was not enqueued ahead"
    eng.close()


def _two_in_flight(gpt, **kw):
    model, params = gpt
    rng = np.random.default_rng(5)
    work = [(rng.integers(0, 64, size=n).astype(np.int32), m)
            for n, m in ((5, 9), (3, 7))]
    eng = ServingEngine(
        model, params, num_slots=2, temperature=0.0, kv_block_size=8, **kw)
    ids = {eng.submit(p, n): (p, n) for p, n in work}
    done = {c.id: c for c in eng.step()}
    done.update((c.id, c) for c in eng.step())
    assert eng._inflight is not None and not done
    return eng, ids


def _resolved_once(eng, ids, done, gpt):
    model, params = gpt
    for c in eng.run():
        assert c.id not in done, f"request {c.id} resolved twice"
        done[c.id] = c
    assert sorted(done) == sorted(ids)
    for rid, (p, n) in ids.items():
        np.testing.assert_array_equal(
            done[rid].tokens, _greedy(model, params, p, n))


@pytest.mark.parametrize("what", ["park_slot", "respread_pool", "close",
                                  "reset_cache", "lower_decode_step"])
def test_drain_rule_lands_the_step_in_flight(gpt, what):
    """``park_slot``, ``respread_pool``, ``close``, ``reset_cache`` and
    ``lower_decode_step`` called with a step in flight land it first: the
    parked tokens are whole (as many as the steps enqueued), and every
    submitted id resolves exactly once, token for token."""
    eng, ids = _two_in_flight(gpt)
    done = {}
    enqueued = eng.stats["decode_steps"] + 1  # fetched, and the one ahead
    if what == "park_slot":
        parked = eng.park_slot(0)
        assert eng._inflight is None
        # The first token came from the prefill, one more from each step.
        assert len(parked["tokens"]) == 1 + enqueued == parked["len"] - \
            parked["req"].prompt.size
        assert parked["last_tok"] == parked["tokens"][-1]
        done.update((c.id, c) for c in eng.step())  # the other row decodes on
        eng.resume_parked(parked, 0)
    elif what == "respread_pool":
        eng.respread_pool(2)
        assert eng._inflight is None
        assert eng.stats["parked"] == eng.stats["resumed"] == 2
    elif what == "close":
        eng.close()
        assert eng._inflight is None
        assert all(len(t) == 1 + enqueued for t in eng._tokens)
    elif what == "lower_decode_step":
        assert "serve_paged_decode" in eng.lower_decode_step().as_text()
        assert eng._inflight is None
    else:
        with pytest.raises(RuntimeError, match="active slots"):
            eng.reset_cache()  # landed, and the rows still live: refused
        assert eng._inflight is None
    if what != "park_slot":
        assert eng.stats["decode_steps"] == enqueued
    _resolved_once(eng, ids, done, gpt)
    if what == "reset_cache":
        # A step whose rows are all in their LAST step: the reset lands it,
        # finds nothing live, and the completions still come back.
        rid = eng.submit(np.arange(4, dtype=np.int32), 3)
        assert eng.step() == [] and eng._inflight is not None
        assert not eng._decoding.any() and eng._active.any()
        eng.reset_cache()
        assert eng._inflight is None and not eng._active.any()
        assert [c.id for c in eng.run()] == [rid]
    eng.close()
