"""Work counts from shapes, against counts worked out by hand, and the
peaks table."""
import chip_bench_setup  # noqa: F401  (puts the benchmark on sys.path)
import json
import pathlib

import pytest

from yardstick import peaks, work

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def test_kernels():
    w = work.matmul(256, 256, 256, 4, 4)
    assert w.flops == 2 * 256 ** 3 == 33_554_432
    assert w.bytes == 3 * 256 * 256 * 4 == 786_432
    w = work.copy((131072, 1024), 4)
    assert w.bytes == 2 * 512 * 2 ** 20         # 512 MiB read, then written
    w = work.sort_rows(32, 1024, 4)
    assert w.bytes == 2 * 32 * 1024 * 4
    assert w.flops == 32 * 512 * 10 * 11 // 2   # n/2 * lg * (lg+1) / 2


def test_least_time_names_its_bound():
    p = peaks.device_peaks("TPU v5 lite")
    t, bound = work.least_time(work.matmul(4096, 4096, 4096, 2, 2), p)
    assert bound == "compute"
    assert t == pytest.approx(2 * 4096 ** 3 / 197e12)
    t, bound = work.least_time(work.copy((131072, 1024), 4), p)
    assert bound == "memory"
    assert t == pytest.approx(2 ** 30 / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.device_peaks("TPU v9 imaginary")


def test_mamba2_780m():
    cfg = json.load(open(CONFIGS / "serve-mamba2-780m.json"))
    d = work.mamba2_dims(cfg)
    assert (d["d_inner"], d["heads"], d["conv_dim"], d["d_in_proj"],
            d["vocab"]) == (3072, 48, 3328, 6448, 50288)
    layers, head = work.mamba2_matmul_params(cfg)
    assert layers == 48 * (1536 * 6448 + 3072 * 1536) == 701_890_560
    assert head == 1536 * 50288 == 77_242_368
    # a 2048-token prefill: every layer over every token, the head once
    assert work.mamba2_prefill(cfg, 2048).flops == \
        2 * (701_890_560 * 2048 + 77_242_368)
    # the state: conv 48*3*3328 bf16, SSD 48*48*128*64 f32
    assert work.mamba2_state_bytes(cfg) == 48 * 3 * 3328 * 2 + \
        48 * 48 * 128 * 64 * 4
    per_layer = 1536 * 6448 + 3072 * 1536 + 4 * 3328 + 3328 + 3 * 48 + \
        1536 + 3072
    params = 48 * per_layer + 1536 * 50288 + 2 * 1536
    dec = work.mamba2_decode(cfg)
    assert dec.bytes == 4 * params + 2 * work.mamba2_state_bytes(cfg)
    assert dec.flops == 2 * (701_890_560 + 77_242_368)
