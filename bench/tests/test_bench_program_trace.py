"""The reduction of the program's own spans and scopes: on hand-made
traces with known answers, on the small recorded v5e trace (which holds
no program span), and on the tick program of a tiny model compiled here,
whose named scopes must change no instruction."""
import contextlib
import os
import re

import jax
import pytest

from bench import harness, program_trace, spec, tracing
from bench.tests.tiny import DATA

CATS = {"tick": "_tick_impl", "prefill": "_prefill_one_impl"}
MS = 1e6  # ns
OFF = -1 * MS          # the device clock stands 1 ms behind the host's
TICKS = (50.4, 60.6, 70.8)
STEPS = (50.1, 60.1, 70.1)


def _ms(a, b):
    return a * MS, b * MS


def _dev(name, a, b):
    return (name, a * MS + OFF, b * MS + OFF)


def synthetic(lag=0.0):
    """One admission (a prefill and an eager scatter inside it), three
    ticks, then a wait, in a 100-ms window; host times in ms.  The first
    device operation starts ``lag`` ms after the first engine call."""
    host = [("bench.window",) + _ms(0, 100), ("bench.admit",) + _ms(10, 40),
            ("bench.wait",) + _ms(80, 95)]
    program = [("serve.admit",) + _ms(10, 39.9),
               ("serve.admit.reap",) + _ms(10, 10.5),
               ("serve.sync",) + _ms(10.1, 10.4),
               ("serve.admit.prefill",) + _ms(10.5, 11),
               ("serve.admit.scatter",) + _ms(11, 12),
               ("serve.admit.first_tokens",) + _ms(12, 39.5)]
    mods = [_dev("jit_set(1)", 10 + lag, 10.2),
            _dev("jit__prefill_one_impl(2)", 11, 25),
            _dev("jit_scatter(3)", 26, 30)]
    ops = [_dev("%scatter.1 = s32[4] scatter()", 10 + lag, 10.2),
           _dev("%while.64 = (bf16[8]) while()", 11, 25),
           _dev("%fusion.5 = bf16[8] fusion()", 11, 18),
           _dev("%dot.3 = bf16[8] dot()", 18, 25),
           _dev("%scatter.4 = bf16[8] scatter()", 26, 30)]
    for t, s in zip(TICKS, STEPS):
        host += [("bench.step",) + _ms(s - 0.1, s + 0.9),
                 ("bench.sync",) + _ms(s + 0.9, s + 9.9)]
        program += [("serve.step",) + _ms(s, s + 0.8),
                    ("serve.sync",) + _ms(s + 1, s + 9.8),
                    ("serve.sync.fetch",) + _ms(s + 1.1, s + 9.4),
                    ("serve.sync.apply",) + _ms(s + 9.4, s + 9.7)]
        mods.append(_dev("jit__tick_impl(7)", t, t + 4.6))
        ops += [_dev("%while.5 = (bf16[8]) while()", t, t + 4.6),
                _dev("%fusion.1 = bf16[8] fusion()", t, t + 2),
                _dev("%copy.7 = bf16[8] copy()", t + 2, t + 3),
                _dev("%fusion.2 = bf16[8] fusion()", t + 3, t + 4),
                _dev("%fusion.9 = bf16[8] fusion()", t + 4, t + 4.6)]
    return {"devices": [{"ops": ops, "modules": mods}], "host": host,
            "program": program}


#: what the tick's HLO says of its operations (fusion.9 is left out)
SCOPES = {"while.5": ("none", "while"), "fusion.1": ("kv_read", "fusion"),
          "copy.7": ("none", "copy"), "fusion.2": ("mlp", "fusion")}


def test_tick_time_by_scope_counts_self_time_only():
    r = program_trace.reduce(synthetic(), CATS, SCOPES)
    ts = r["tick_scopes"]
    assert ts["ticks"] == 3
    assert ts["seconds"] == {"kv_read": pytest.approx(0.006),
                             "none": pytest.approx(0.003),
                             "mlp": pytest.approx(0.003)}
    assert r["tick_scope_coverage"] == pytest.approx(12 / 13.8)


def test_a_container_is_left_out_by_name_where_the_hlo_lacks_it():
    scopes = {k: v for k, v in SCOPES.items() if k != "while.5"}
    r = program_trace.reduce(synthetic(), CATS, scopes)
    assert r["tick_scopes"]["seconds"]["none"] == pytest.approx(0.003)
    assert r["tick_scope_coverage"] == pytest.approx(12 / 13.8)


def test_no_scope_map_no_tick_scopes():
    r = program_trace.reduce(synthetic(), CATS)
    assert "tick_scopes" not in r and "tick_scope_coverage" not in r


def test_admission_device_time_splits_prefill_from_the_rest():
    a = program_trace.reduce(synthetic(), CATS)["admit_device"]
    assert a["calls"] == 1 and a["prefill_spans"] == 1
    assert a["prefill_s"] == pytest.approx(0.014)
    # the eager writes inside the span: 0.2 + 4 ms; no tick operation
    assert a["other_s"] == pytest.approx(0.0042)
    assert a["idle_s"] == pytest.approx(0.0299 - 0.0182)


def test_idle_goes_to_the_innermost_span_and_sums_to_the_window():
    t = synthetic()
    r = program_trace.reduce(t, CATS)
    base = tracing.reduce(t, CATS)
    idle = r["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(base["window_s"]
                                               - base["busy_s"])
    assert idle["host:unannotated"] == pytest.approx(0.025)
    assert idle["bench.wait"] == pytest.approx(0.015)
    assert idle["serve.admit.prefill"] == pytest.approx(0.0005)
    # the leading sync inside reap, then reap's own tail
    assert idle["serve.sync"] == pytest.approx(0.0002 + 3 * 0.0001)
    assert idle["serve.admit.reap"] == pytest.approx(0.0001)
    assert idle["serve.admit"] == pytest.approx(0.0004)
    # each serve.step sits inside a bench.step that starts 0.1 ms earlier
    assert idle["serve.step"] == pytest.approx((0.3 + 0.5 + 0.7) / 1e3)
    assert idle["bench.step"] == pytest.approx(0.0003)
    assert idle["serve.sync.apply"] == pytest.approx(0.0009)


def test_span_self_time_leaves_out_child_spans():
    s = program_trace.reduce(synthetic(), CATS)["span_self_s"]
    assert s["bench.window"] == pytest.approx(0.1 - 0.03 - 0.03 - 0.015)
    assert s["bench.admit"] == pytest.approx(0.0001)
    assert s["serve.admit"] == pytest.approx(0.0004)
    assert s["serve.admit.reap"] == pytest.approx(0.0002)
    assert s["serve.sync"] == pytest.approx(0.0003 + 3 * 0.0002)
    assert s["serve.sync.fetch"] == pytest.approx(3 * 0.0083)


@pytest.mark.parametrize("lag,low", [(0.0, 0.3), (0.2, 0.1)])
def test_clock_check_reads_the_shift_error(lag, low):
    """Ticks start 0.3 / 0.5 / 0.7 ms after their serve.step; a first
    device operation that lags the first call moves every host span by
    the lag, and the check reads it."""
    c = program_trace.reduce(synthetic(lag), CATS)["clock_check_ms"]
    assert c["pairs"] == 3
    assert c["min"] == pytest.approx(low)
    assert c["median"] == pytest.approx(low + 0.2)


def test_an_operation_across_two_admissions_is_split_between_them():
    """A long operation counts in each admission span it overlaps, and
    only for the part inside it."""
    t = synthetic()
    t["program"] += [("serve.admit",) + _ms(41, 44)]
    t["devices"][0]["modules"].append(_dev("jit_copy(4)", 38, 43))
    t["devices"][0]["ops"].append(_dev("%copy.1 = bf16[8] copy()", 38, 43))
    a = program_trace.reduce(t, CATS)["admit_device"]
    assert a["calls"] == 2
    assert a["other_s"] == pytest.approx(0.0042 + 0.0019 + 0.002)
    assert a["idle_s"] == pytest.approx(0.0299 - 0.0182 - 0.0019
                                        + 0.003 - 0.002)


def test_existing_keys_do_not_see_the_program_spans():
    t = synthetic()
    bare = dict(t)
    del bare["program"]
    assert tracing.reduce(t, CATS) == tracing.reduce(bare, CATS)


@pytest.fixture(scope="module")
def recorded():
    return program_trace.read_xspace(os.path.join(DATA, "small.xplane.pb"))


def test_recorded_trace_without_program_spans(recorded):
    assert recorded["program"] == []
    base = tracing.reduce(recorded, CATS)
    r = program_trace.reduce(recorded, CATS)
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-9)
    assert r["admit_device"]["prefill_spans"] == 0
    assert r["span_self_s"]["bench.step"] > 0


def test_one_parse_reads_what_the_harness_reader_reads(recorded):
    """``read_xspace`` parses the file once and returns the harness
    reader's keys unchanged."""
    base = tracing.read_xspace(os.path.join(DATA, "small.xplane.pb"))
    assert recorded["devices"] == base["devices"]
    assert recorded["host"] == base["host"]


def _sources():
    from bench.tests import test_bench_tracing
    return {"lag-0": synthetic(), "lag-0.2": synthetic(0.2),
            "tracing-synthetic": test_bench_tracing.synthetic(),
            "recorded": program_trace.read_xspace(
                os.path.join(DATA, "small.xplane.pb"))}


@pytest.mark.parametrize("source", ["lag-0", "lag-0.2", "tracing-synthetic",
                                    "recorded"])
def test_window_and_ownership_agree_with_the_harness_reduction(source):
    """The window, the shift and each operation's owner are copies of
    ``bench.tracing.reduce``'s: its window, busy time and device time by
    category come out the same from them."""
    t = _sources()[source]
    base = tracing.reduce(t, CATS)
    lo, hi, _ = program_trace._window(t)
    assert (hi - lo) / 1e9 == pytest.approx(base["window_s"], rel=1e-12)
    busy = 0.0
    per_cat: dict = {}
    for dev in t["devices"]:
        owned = program_trace._owned_ops(dev, lo, hi, CATS)
        busy += tracing._length(tracing.union(
            [(s, e) for _, _, s, e in owned]))
        by_cat: dict = {}
        for cat, _, s, e in owned:
            by_cat.setdefault(cat, []).append((s, e))
        for cat, ivs in by_cat.items():
            per_cat[cat] = per_cat.get(cat, 0.0) + tracing._length(
                tracing.union(ivs)) / 1e9 / len(t["devices"])
    assert busy / len(t["devices"]) / 1e9 == pytest.approx(base["busy_s"],
                                                           rel=1e-12)
    assert set(per_cat) == set(base["modules"])
    for cat, v in per_cat.items():
        assert v == pytest.approx(base["modules"][cat]["device_s"],
                                  rel=1e-12)


# ---------------------------------------------------------------------------
# the tick's HLO: scopes are metadata only, and the parser finds them
# ---------------------------------------------------------------------------

VARIANTS = {"paged": (8, False), "dense": (None, False),
            "paged-int8": (8, True)}


def _tick_text(variant):
    from repro.models import build_model
    from repro.models.config import ModelConfig, ParallelConfig
    from repro.serve import BatchedEngine, Request, ServeConfig
    ps, int8 = VARIANTS[variant]
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=256, dtype="float32")
    model = build_model(cfg, ParallelConfig(remat="none",
                                            kv_cache_int8=int8))
    params = model.init_params(jax.random.PRNGKey(0))
    eng = BatchedEngine(model, params, ServeConfig(
        batch_slots=2, max_seq_len=32, eos_id=-1, page_size=ps,
        num_pages=8 if ps else None))
    eng.admit([Request(rid=0, prompt=list(range(2, 11)),
                       max_new_tokens=4)])
    return eng.tick_hlo_text()


def _instructions(text):
    """Instruction lines with metadata stripped and names renumbered: the
    name stack feeds XLA's name uniquifier, nothing else."""
    out = []
    for line in text.splitlines():
        if " = " not in line:
            continue
        line = re.sub(r", metadata=\{[^}]*\}", "", line)
        out.append(re.sub(r"([\w\-]+)\.\d+\b", r"\1", line))
    return out


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_scopes_leave_the_compiled_tick_unchanged(variant, monkeypatch):
    scoped = _tick_text(variant)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _tick_text(variant)
    assert "/kv_read/" in scoped and "/kv_read/" not in bare
    assert _instructions(scoped) == _instructions(bare)


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_the_parser_finds_each_scope_of_the_tick(variant):
    scopes = program_trace.scope_map(_tick_text(variant))
    found = {s for s, _ in scopes.values()}
    assert found == program_trace.SCOPES | {"none"}
    whiles = [n for n, (_, op) in scopes.items() if op == "while"]
    assert whiles and all(scopes[n][0] == "none" for n in whiles)


def test_scope_map_reads_the_innermost_scope():
    text = """HloModule m
ENTRY %main {
  %p = f32[2]{0} parameter(0)
  %fusion.3 = f32[2]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(t)/while/body/attention/kv_read/dot_general" source_line=3}
  ROOT %w.1 = (f32[2]{0}, s32[]) while(%t), condition=%c, body=%b
  %copy.2 = f32[2]{0} copy(%p), metadata={op_name="jit(t)/while/body/mlp/x/head_like"}
}"""
    m = program_trace.scope_map(text)
    assert m["fusion.3"] == ("kv_read", "fusion")
    assert m["w.1"] == ("none", "while")
    assert m["copy.2"] == ("mlp", "copy")
    assert m["p"] == ("none", "parameter")


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def _ctx(trace):
    return harness.LayerCtx(cfg={}, records=[], window_s=1.0, give_up=1.0,
                            admit_calls=[], prefill_lens=[], tick_work=[],
                            tick_stats=[], shared_hits=0, prompt_pages=0,
                            trace=trace, peak=None)


READS = {"decode_kv_read_ms": 2.0, "decode_unscoped_ms": 1.0,
         "admit_other_ms_per_req": 4.2, "admit_idle_ms_per_req": 11.7}


@pytest.mark.parametrize("name", list(READS))
def test_reader_reads_the_reduction(name):
    r = program_trace.reduce(synthetic(), CATS, SCOPES)
    reader = spec.load_metric(name)
    assert reader.UNIT == "ms"
    assert reader.read(_ctx(r)) == pytest.approx(READS[name])


@pytest.mark.parametrize("name", list(READS))
def test_reader_finds_nothing_without_its_key(name):
    reader = spec.load_metric(name)
    assert reader.read(_ctx(None)) is None
    assert reader.read(_ctx(tracing.reduce(synthetic(), CATS))) is None
