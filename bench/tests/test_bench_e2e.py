"""End-to-end arithmetic: tails over every request, rates over the whole
window, so that a stall anywhere in the window moves them."""
import pytest

from bench import e2e, roofline


def steady(n=100, window=10.0, stall_at=None, stall=0.0, gap=0.01):
    """``n`` requests, one every window/n seconds, each served 10 tokens
    ``gap`` apart; a stall delays every delivery after ``stall_at``."""
    recs = []
    for i in range(n):
        due = i * window / n
        r = e2e.Record(i, due, 64, 10)
        for k in range(10):
            t = due + 0.02 + gap * k
            if stall_at is not None and t >= stall_at:
                t += stall
            r.deliver(t, 1, window)
        recs.append(r)
    return recs


def test_percentiles_count_every_request():
    recs = steady()
    ttft = e2e.ttft_s(recs, give_up=20.0)
    assert len(ttft) == 100
    assert e2e.percentile(ttft, 95) == pytest.approx(0.02)
    assert e2e.percentile(e2e.tpot_s(recs), 95) == pytest.approx(0.01)


def test_a_stall_moves_the_tail_and_the_rate():
    names = ["ttft_p95_ms", "tpot_p95_ms", "output_tok_s"]
    base = e2e.summarize(steady(n=20, gap=0.1), 10.0, 20.0, names)
    stalled = e2e.summarize(steady(n=20, gap=0.1, stall_at=8.0, stall=2.0),
                            10.0, 20.0, names)
    assert stalled["ttft_p95_ms"] > base["ttft_p95_ms"] + 100
    assert stalled["tpot_p95_ms"] > base["tpot_p95_ms"] * 1.05
    assert stalled["output_tok_s"] < base["output_tok_s"]


def test_unfinished_requests_count_at_give_up():
    recs = steady(n=20)
    for r in recs[:2]:
        r.first = None
    ttft = e2e.ttft_s(recs, give_up=100.0)
    assert max(ttft) == pytest.approx(100.0 - recs[0].due)


def test_rate_is_over_the_whole_window():
    recs = steady(n=10, window=10.0)
    assert e2e.output_tok_s(recs, 10.0) == pytest.approx(10.0)
    assert e2e.output_tok_s(recs, 20.0) == pytest.approx(5.0)


def test_decode_bytes_count_live_kv_not_capacity():
    cfg = {"hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
           "vocab_size": 512, "num_hidden_layers": 2,
           "torch_dtype": "bfloat16"}
    f1, b1 = roofline.decode_step(cfg, [10])
    f2, b2 = roofline.decode_step(cfg, [20])
    kv = roofline.kv_bytes_per_token(cfg)
    assert kv == 2 * 2 * 2 * 16 * 2
    assert b2 - b1 == 10 * kv
    assert f2 - f1 == 2 * 4 * 4 * 16 * 10
    pf, pb = roofline.prefill(cfg, 8)
    assert pf == 2 * roofline.block_params(cfg) * 8 \
        + 2 * 4 * 4 * 16 * 36 + 2 * 64 * 512


def test_unknown_device_kind_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
