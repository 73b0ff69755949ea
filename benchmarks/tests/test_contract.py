"""BENCHMARK.json against the contract's letter, and the data files against it."""

import glob
import io
import os
import re
import tokenize

import pytest

from lib import common, roofline
from lib.common import percentile
from lib.traffic import serve_schedule, synthetic_lm_batch, warmup_prompt_lengths

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = common.load_benchmark()


def _metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


def test_names_and_units_use_the_allowed_characters():
    names = [m["name"] for m in _metrics()]
    names += [w["name"] for w in BENCH["workloads"]] + [c["name"] for c in BENCH["configs"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    assert len(set(m["name"] for m in _metrics())) == len(_metrics())
    assert all(UNIT.match(m["unit"]) for m in _metrics())
    assert all(m["better"] in ("lower", "higher") for m in _metrics())
    for w in BENCH["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and w["chips"] in (1, 4)


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        reported_in = moved.get("workloads", cells)
        assert set(m["workloads"]) <= set(reported_in), m["name"]
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    assert all(0.01 <= m["bound"] <= 0.1 for m in BENCH["end_to_end"])


def test_per_layer_entries_agree_with_the_metric_files():
    import run

    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for w in BENCH["workloads"]:
        cell = common.load_cell(w["name"])
        for spec in run.metric_specs(cell):
            entry = by_name[spec["name"]]
            assert w["name"] in entry["workloads"]
            for key in ("unit", "better", "source", "layer", "moves"):
                assert entry[key] == spec[key], (spec["name"], key)
    for m in BENCH["per_layer"]:
        for cell_name in m["workloads"]:
            cell = common.load_cell(cell_name)
            assert m["name"] in [s["name"] for s in run.metric_specs(cell)]


def test_every_metric_file_finds_its_reader():
    import run

    for path in sorted(glob.glob(os.path.join(common.BENCH_DIR, "metrics", "*.json"))):
        spec = common.load_json("metrics", os.path.basename(path))
        assert callable(run.reader_for(spec)), spec["name"]
    shared = [common.load_json("metrics", n + ".json").get("reader_file") for n in (
        "graft_program_device_ms", "flash_fwd_ms.train", "flash_bwd_ms.train")]
    assert shared == ["decode_program_device_ms", "kernel_ms", "kernel_ms"]
    with pytest.raises(FileNotFoundError):
        run.reader_for({"name": "x", "reader_file": "no_such_reader"})


MODEL_NAME = re.compile(r"gpt2|GPTConfig|\bGPT\b")


def test_the_harness_holds_no_models_name():
    """run.py, lib/ and metrics/ take a configuration's model and reference
    from its files: outside comments and docstrings no line of them names a
    model (a reference module, a model class or its config class)."""
    paths = [os.path.join(common.BENCH_DIR, "run.py")]
    for sub in ("lib", "metrics"):
        paths += sorted(glob.glob(os.path.join(common.BENCH_DIR, sub, "*.py")))
    assert len(paths) > 10
    named = []
    for path in paths:
        with open(path) as fh:
            source = fh.read()
        prev = None
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            prose = tok.type == tokenize.COMMENT or (
                tok.type == tokenize.STRING and (prev is None or prev in (
                    tokenize.NEWLINE, tokenize.INDENT, tokenize.DEDENT)))  # a docstring
            if tok.type not in (tokenize.NL, tokenize.COMMENT):
                prev = tok.type
            if not prose and MODEL_NAME.search(tok.string):
                named.append((os.path.relpath(path, common.BENCH_DIR), tok.start[0], tok.string))
    assert not named, named


def test_config_flops_equal_their_stated_arithmetic():
    train = common.load_json("configs", "gpt2m-train.json")
    m = train["model"]
    l, d, v, t = m["num_layers"], m["hidden_dim"], m["vocab_size"], m["seq_len"]
    assert train["flops"]["per_sample"] == 6 * (l * 12 * d * d + v * d) * t + 3 * l * 2 * t * t * d
    serve = common.load_json("configs", "gpt2m-serve.json")
    assert serve["flops"]["per_token"] == 2 * (l * 12 * d * d + v * d)
    for cfg in (train, serve):
        pub = cfg["published"]
        assert (m["num_layers"], m["hidden_dim"], m["num_heads"], m["seq_len"], m["vocab_size"]) == (
            pub["n_layer"], pub["n_embd"], pub["n_head"], pub["n_positions"], pub["vocab_size"])


def test_peaks_table_refuses_an_unknown_device():
    assert common.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        common.peaks_for("cpu")


def test_percentile_is_exact_on_fixed_inputs():
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    assert percentile([7.0], 95) == 7.0
    assert percentile([0, 10], 95) == pytest.approx(9.5)


def test_schedule_has_the_same_work_for_every_seed_in_another_order():
    mix = common.load_json("traffic", "chat-steady.json")
    a = serve_schedule(mix, 10.0, 1, 50257)
    b = serve_schedule(mix, 10.0, 2**31 + 11, 50257)
    due_in = lambda s: [r for r in s if 0.0 <= r["due_s"] < 10.0]
    assert len(due_in(a)) == len(due_in(b)) == round(mix["rate_per_s"] * 10.0)
    assert len(a) == len(b) == round(mix["rate_per_s"] * (10.0 + mix["ramp_s"]))
    key = lambda s: sorted(len(r["prompt"]) for r in due_in(s))
    assert key(a) == key(b)
    assert [len(r["prompt"]) for r in due_in(a)] != [len(r["prompt"]) for r in due_in(b)]
    assert sorted(r["max_new"] for r in due_in(a)) == sorted(r["max_new"] for r in due_in(b))
    assert -mix["ramp_s"] <= a[0]["due_s"] < 0 and due_in(a)[0]["due_s"] == 0.0
    assert all(x["due_s"] <= y["due_s"] for x, y in zip(a, a[1:]))
    lo, hi = mix["prompt_tokens"]["low"], mix["prompt_tokens"]["high"]
    assert all(lo <= len(r["prompt"]) <= hi and len(r["prompt"]) + r["max_new"] <= 1024 for r in a)
    again = serve_schedule(mix, 10.0, 1, 50257)
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, again))


@pytest.mark.parametrize("name", ["chat-steady", "conv-mixed-steady"])
def test_a_mix_with_an_order_seed_has_one_order_for_every_seed(name):
    """`order_seed` fixes which request comes when; the run's seed still
    draws the token ids. Without it the order follows the run's seed."""
    mix = dict(common.load_json("traffic", name + ".json"), order_seed=7)
    a = serve_schedule(mix, 10.0, 1, 50257)
    b = serve_schedule(mix, 10.0, 2**31 + 11, 50257)
    shape = lambda s: [(r["due_s"], len(r["prompt"]), r["max_new"]) for r in s]
    assert shape(a) == shape(b)
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, b))
    c = serve_schedule(dict(mix, order_seed=8), 10.0, 1, 50257)
    assert shape(c) != shape(a)
    free = {k: v for k, v in mix.items() if k != "order_seed"}
    assert shape(serve_schedule(free, 10.0, 1, 50257)) != shape(
        serve_schedule(free, 10.0, 2**31 + 11, 50257))


def test_open_loop_times_from_the_due_moment_and_reports_lateness():
    """A generator that runs late: TTFT is taken from when the request was
    due, and the lateness is reported."""
    from types import SimpleNamespace

    from lib.serve import window_metrics

    res = {
        "t_end": 12.0,
        "requests": {
            1: {"due_s": 1.0, "submit_s": 1.5, "prompt": [0] * 4, "max_new": 3},
            2: {"due_s": 2.0, "submit_s": 2.0, "prompt": [0] * 4, "max_new": 2},
            3: {"due_s": -1.0, "submit_s": -1.0, "prompt": [0] * 4, "max_new": 2},
            4: {"due_s": 3.0, "submit_s": 3.0, "prompt": [0] * 4, "max_new": 2},
        },
        "done": {
            1: SimpleNamespace(ok=True, token_times_s=[0.2, 0.3, 0.5], prompt_len=4),
            2: SimpleNamespace(ok=True, token_times_s=[0.1, 0.4], prompt_len=4),
            3: SimpleNamespace(ok=True, token_times_s=[0.5, 1.5], prompt_len=4),
        },
    }
    w = window_metrics(res, 10.0)
    assert (w["attempted"], w["failed"]) == (3, 1)  # request 4 never finished
    assert sorted(w["gen_late_s"]) == [0.0, 0.0, 0.5]
    assert sorted(w["ttft_s"]) == pytest.approx([0.1, 0.7, 9.0])  # 1.5+0.2-1.0; 12-3
    assert sorted(w["gaps_s"]) == pytest.approx([0.1, 0.2, 0.3])
    assert w["serve_tokens_per_s"] == pytest.approx(6 / 10.0)  # request 3's second token too
    assert (w["backlog_mid"], w["backlog_at_close"]) == (1, 1)


def test_lengths_are_the_quantiles_of_the_stated_lognormal():
    from lib.traffic import _quantiles

    spec = {"dist": "lognormal", "mean": 161.31, "sigma": 1.0, "low": 1, "high": 10**6}
    x = _quantiles(spec, 4000)
    assert x.mean() == pytest.approx(161.31, rel=0.01)  # unclipped: the stated mean
    assert sorted(x)[2000] == pytest.approx(161.31 / 2.718281828 ** 0.5, rel=0.01)  # its median
    clipped = _quantiles(dict(spec, low=4, high=512), 4000)
    assert clipped.min() >= 4 and clipped.max() == 512 and clipped.mean() < x.mean()
    with pytest.raises(ValueError):
        _quantiles(dict(spec, dist="uniform"), 10)


def test_pool_fill_by_hand():
    """Two requests on a pool of 10 blocks of 4 positions: request 1 (prompt
    6, 5 tokens) owns 3 blocks from its first token to its last; request 2
    (prompt 3, 2 tokens) owns 1."""
    from types import SimpleNamespace

    from lib.serve import pool_fill

    res = {
        "requests": {
            1: {"due_s": 0.0, "submit_s": 0.0, "prompt": [0] * 6, "max_new": 5},
            2: {"due_s": 0.0, "submit_s": 1.0, "prompt": [0] * 3, "max_new": 2},
            3: {"due_s": 0.0, "submit_s": 2.0, "prompt": [0] * 3, "max_new": 2},
        },
        "done": {
            1: SimpleNamespace(token_times_s=[1.0, 2.0, 3.0, 4.0, 5.0]),
            2: SimpleNamespace(token_times_s=[1.5, 2.5]),
        },
    }
    # time: blocks holding positions / blocks owned, after the event
    # 1.0: 2 / 3 (request 1 in: positions 0..5)   2.0: 2 / 3
    # 2.5: 3 / 4 (request 2 in: positions 0..2)   3.0: 3 / 4
    # 3.5: 2 / 3 (request 2 out)   4.0: 3 / 3 (request 1 writes position 8)
    # 5.0: 0 / 0 (request 1 out); request 3 never answered and counts nowhere
    fill = pool_fill(res, 10.0, 4, 10)
    assert fill["reserved_peak_share"] == pytest.approx(0.4)
    assert fill["held_mean_share"] == pytest.approx((2 + 2 + 3 + 3 + 2 + 3 + 0) / 7 / 10)
    assert pool_fill(res, 10.0, 4, 0) is None


def test_warmup_covers_every_block_count_of_the_mix():
    chat = common.load_json("traffic", "chat-steady.json")
    for mix in (chat, dict(chat, prompt_tokens=dict(chat["prompt_tokens"], low=384, high=896))):
        lens = warmup_prompt_lengths(mix, 16, 1024)
        lo, hi = mix["prompt_tokens"]["low"], mix["prompt_tokens"]["high"]
        assert {-(-n // 16) for n in lens} == {-(-n // 16) for n in range(lo, hi + 1)}


def test_synthetic_rows_equal_the_programs_corpus():
    from frl_distributed_ml_scaffold_tpu.config.schema import DataConfig
    from frl_distributed_ml_scaffold_tpu.data.synthetic import SyntheticLM

    cfg = DataConfig(name="lm_synthetic", global_batch_size=4, seq_len=32,
                     vocab_size=97, shuffle_seed=2**31 + 5)
    theirs = SyntheticLM(cfg, split="train").batch(3, 4)["tokens"]
    ours = synthetic_lm_batch(2**31 + 5, 3, 4, 32, 97)
    assert (theirs == ours).all()
    assert len({tuple(r) for r in ours}) == 4  # rows all differ


def test_roofline_counts_come_from_the_calls_shapes():
    flops, nbytes = roofline.causal_attention_train(8, 16, 1024, 64, 24)
    assert flops == 8 * 16 * 24 * 6 * (2 * 1024 * 1024 * 64 // 2)
    assert nbytes == 8 * 16 * 24 * 12 * 1024 * 64 * 2
    peaks = common.peaks_for("TPU v5 lite")
    least, bound = roofline.least_seconds(flops, nbytes, peaks)
    assert bound == "compute" and least == pytest.approx(flops / 197e12)
    f2, b2 = roofline.paged_decode_attention(1000, 16, 64, 24)
    assert b2 == 1000 * 24 * 2 * 16 * 64 * 2 and roofline.least_seconds(f2, b2, peaks)[1] == "memory"
