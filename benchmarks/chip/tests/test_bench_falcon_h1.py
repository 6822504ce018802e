"""The Falcon-H1 serving cell on the CPU at a shrunk size: a sound run is
correct, the fp8 control and every fault are not, the cell's readers read
nothing without a trace and the declared work from a stand-in trace, and the
work counts match counts worked out by hand."""
from chip_bench_setup import BENCH_DIR, ROOT  # first: puts the benchmark on sys.path
import json
import types

import pytest

import bench
from yardstick import falcon_h1_work as work
from yardstick.peaks import device_peaks

CELL = "serve-falcon-h1-34b.longdoc-steady"
SEED = 2**31 + 4321          # larger than 32 signed bits hold
CONFIG = json.loads((BENCH_DIR / "configs" / "serve-falcon-h1-34b.json")
                    .read_text())
SHRUNK = {
    # 4 layers at a quarter of the heads and a twentieth of the widths.  A
    # sound run reads a gap of 0 to 0.028 here and the fp8 control 0.92 to
    # 1.05 (seeds 2**31 + 4321, 7, 123456), so this size has a limit of its
    # own: the cell's is set from readings at the published widths
    "config": {"hidden_size": 256, "num_attention_heads": 5,
               "num_key_value_heads": 1, "head_dim": 64, "mamba_d_ssm": 256,
               "mamba_n_heads": 4, "mamba_d_head": 64, "mamba_d_state": 32,
               "intermediate_size": 1024, "vocab_size": 1024,
               "mamba_chunk_size": 16,
               "limits": {"served_logit_gap_max": 0.3}},
    "traffic": {"prompt_lengths": [32, 64], "prompt_weights": [0.5, 0.5],
                "output_min": 8, "output_max": 16, "rate_per_s": 16,
                "check_requests": 3},
}


def run_shrunk(**kw):
    return bench.run(ROOT, CELL, SEED, 0.5, trace=False, require_tpu=False,
                     use_cache=False, overrides=SHRUNK, **kw)


def test_sound_run_is_correct():
    line = run_shrunk()
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "ttft_p90_s", "sojourn_p90_s",
                                    "tokens_per_s"}


@pytest.mark.parametrize("fault", [None, "alter_token", "unchanged_state",
                                   "dropped_kv", "dropped_ssd_state"])
def test_control_and_faults_are_not_correct(fault):
    """``None`` is the fp8 control, which the check reads in the program's
    place; the faults break the timed path."""
    line = run_shrunk(**({"control": True} if fault is None
                         else {"fault": fault}))
    assert not line["correct"], line["checks"]
    gap = line["checks"]["served_logit_gap_max"]
    assert gap["value"] > gap["limit"]


READERS = ["mfu.prefill.falcon-h1", "hbm_share.decode.falcon-h1",
           "flash_attention_roofline"]


@pytest.mark.parametrize("name", READERS)
def test_reader_without_trace_reads_nothing(name):
    reader = bench.load_module(BENCH_DIR / "metrics" / f"{name}.py")
    run = bench.ReaderRun(cell=object(), trace=None, peaks=None, config={},
                          traffic={})
    assert reader.read(run) is None


class _Cell:
    """What the binding's ``Cell`` gives the readers, for two 4096-token
    prefills and three decode steps."""

    prefill_lengths = [4096, 4096]
    decode_positions = [4096, 4097, 4098]

    def prefill_work(self):
        return work.Work(sum(work.prefill(CONFIG, L).flops
                             for L in self.prefill_lengths), 0.0)

    def decode_bytes(self):
        return [work.decode(CONFIG, p).bytes for p in self.decode_positions]

    def attention_calls(self):
        return [work.attention(CONFIG, L) for L in self.prefill_lengths
                for _ in range(4)]


def test_readers_divide_declared_work_by_device_time():
    peaks = device_peaks("TPU v5 lite")
    trace = types.SimpleNamespace(
        module=lambda name: {"falcon_h1_prefill": (0.5, 2),
                             "falcon_h1_decode": (0.015, 3)}[name],
        op_ns={"flash_attention.3": 2e8, "flash_attention": 1e8,
               "fusion.12": 5e9})
    run = bench.ReaderRun(cell=_Cell(), trace=trace, peaks=peaks,
                          config=CONFIG, traffic={})
    read = {n: bench.load_module(BENCH_DIR / "metrics" / f"{n}.py").read(run)
            for n in READERS}
    cell = _Cell()
    assert read["mfu.prefill.falcon-h1"] == pytest.approx(
        100 * cell.prefill_work().flops / 0.5 / 197e12)
    assert read["hbm_share.decode.falcon-h1"] == pytest.approx(
        100 * sum(cell.decode_bytes()) / 0.015 / 819e9)
    # 8 calls of 2 * 4096^2 * 2560 FLOPs, compute-bound, over 0.3 s
    assert read["flash_attention_roofline"] == pytest.approx(
        100 * 8 * 2 * 4096 ** 2 * 2560 / 197e12 / 0.3)


def test_work_counts_by_hand():
    """At the configuration's sizes: 4 layers of hidden 5120, GQA 20/4 of
    128, SSM d_ssm 4096 (32 heads) with d_state 256 in 2 groups, MLP 21504,
    a 32640-row vocabulary slice."""
    per_layer = (5120 * (2560 + 512 + 512) + 2560 * 5120        # attention
                 + 5120 * (4096 + 4096 + 2 * 2 * 256 + 32)      # in_proj
                 + 4096 * 5120                                  # out_proj
                 + 3 * 5120 * 21504)                            # MLP
    assert per_layer == 430_080_000
    assert work.matmul_params(CONFIG) == (4 * per_layer, 5120 * 32640)
    assert work.prefill(CONFIG, 4096).flops == (
        2 * (4 * per_layer * 4096 + 5120 * 32640) + 4 * 2 * 4096 ** 2 * 2560)
    att = work.attention(CONFIG, 32768)
    assert att.flops == 2 * 32768 ** 2 * 2560
    assert att.bytes == 2 * 32768 * (2 * 2560 + 2 * 512)
    conv_dim = 4096 + 2 * 2 * 256
    params = (2 * (4 * per_layer + 5120 * 32640 + 2 * 5120)
              + 4 * (2 * (4 * conv_dim + conv_dim + 2 * 5120 + 4096)
                     + 4 * 3 * 32))
    state = 4 * (3 * conv_dim * 2 + 32 * 256 * 128 * 4)
    # the 4100th token: 4101 positions read, one written, in 4 layers
    kv = 4 * 2 * 512 * 2 * (4099 + 2)
    dec = work.decode(CONFIG, 4099)
    assert dec.bytes == params + kv + 2 * state
    assert dec.flops == 2 * (4 * per_layer + 5120 * 32640)
