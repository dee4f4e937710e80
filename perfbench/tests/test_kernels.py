"""Each named kernel's seconds, calls and roofline share (CPU, no chip):
the name's stem, the reduction, the ``gpt2`` family's counts of the three
flash kernels against numbers worked by hand, and the share a metric file
reads from them."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from perfbench import families, flops, readers, xplane  # noqa: E402

MS = 1e6   # ns
#: an ``XLA Ops`` event of pretrain_1chip on the v5e, as PR 24 read it by hand
FLASH_FWD = ('%flash_fwd.40 = (bf16[128,1024,128]{2,1,0:T(8,128)(2,1)}, '
             'f32[128,1,1024]{2,1,0:T(1,128)}) custom-call(bf16[128,1024,128]'
             '{2,1,0:T(8,128)(2,1)} %bitcast.3505), '
             'custom_call_target="tpu_custom_call"')


def kernel(name, start_ms, dur_ms):
    return [f'%{name} = bf16[128,1024,128]{{2,1,0}} custom-call(bf16[128,1024,'
            f'128]{{2,1,0}} %q), custom_call_target="tpu_custom_call"',
            start_ms * MS, dur_ms * MS]


def load(kind, name):
    with open(os.path.join(BENCH_DIR, kind, name + ".json")) as f:
        return json.load(f)


def test_kernel_name_stem():
    assert xplane.kernel_stem(FLASH_FWD) == "flash_fwd"
    assert xplane.short_name(FLASH_FWD) == "flash_fwd_bf16_128_1024_128_mosaic"
    assert xplane.kernel_stem(kernel("flash_bwd_dkv.7", 0, 1)[0]) == \
        "flash_bwd_dkv"
    assert xplane.kernel_stem(kernel("paged_decode_attn", 0, 1)[0]) == \
        "paged_decode_attn"
    assert xplane.kernel_stem("not an instruction") == ""


def test_every_named_kernel_is_reduced_not_the_largest_ops():
    # device 0: two whole flash_fwd calls, one the window cuts in half,
    # one layer_norm_fwd; device 1: two flash_fwd calls. top=1 keeps one op
    # in the breakdown and every kernel by name.
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        [xplane.WINDOW_SPAN, 0, 100 * MS]]}]}
    dev0 = [kernel("flash_fwd.1", 10, 4), kernel("flash_fwd.2", 20, 4),
            kernel("flash_fwd.3", 98, 4), kernel("layer_norm_fwd.9", 40, 1),
            ["%fusion.5 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p), kind=kLoop",
             50 * MS, 30 * MS]]
    dev1 = [kernel("flash_fwd.1", 10, 4), kernel("flash_fwd.2", 20, 6)]
    r = xplane.reduce({"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": dev0}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": dev1}]},
        host]}, top=1)
    assert len(r["device_ops"]) == 1
    assert r["kernel_s.flash_fwd"] == pytest.approx((4 + 4 + 2 + 4 + 6) / 2 / 1e3)
    assert r["kernel_calls.flash_fwd"] == pytest.approx((2.5 + 2) / 2)
    assert r["kernel_s.layer_norm_fwd"] == pytest.approx(0.5 / 1e3)
    assert r["kernel_calls.layer_norm_fwd"] == pytest.approx(0.5)
    assert r["mosaic_s"] == pytest.approx(
        r["kernel_s.flash_fwd"] + r["kernel_s.layer_norm_fwd"])


#: bh = 8 x 16 = 128, s = 1024, d = 128; one causal matmul is
#: 2 * 128 * 1024^2 * 128 / 2 = 17,179,869,184 flops; a bf16 [bh, s, d]
#: array is 33,554,432 bytes, a float32 [bh, s] row block 524,288
BY_HAND = {
    "flash_fwd": (2 * 17_179_869_184, 4 * 33_554_432 + 1 * 524_288),
    "flash_bwd_dq": (3 * 17_179_869_184, 5 * 33_554_432 + 2 * 524_288),
    "flash_bwd_dkv": (4 * 17_179_869_184, 6 * 33_554_432 + 2 * 524_288),
}


@pytest.mark.parametrize("cell_config,job", [
    ("cgpt-1p3b-d20", "pretrain_1chip"), ("cgpt-1p3b", "pretrain_zero2_dp4")])
@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_gpt2_kernel_counts_against_numbers_worked_by_hand(name, cell_config,
                                                           job):
    cfg, traffic = load("configs", cell_config), load("traffic", job)
    got = families.load(cfg).kernel_counts(name, cfg, traffic)
    assert got == BY_HAND[name]       # per chip: the depth does not enter


def test_no_count_for_a_kernel_whose_work_depends_on_data_or_for_serving():
    cfg = load("configs", "cgpt-1p3b")
    family = families.load(cfg)
    assert family.kernel_counts("paged_decode_attn", cfg,
                                load("traffic", "pretrain_1chip")) is None
    assert family.kernel_counts("flash_fwd", cfg,
                                load("traffic", "docs_offline")) is None


def test_roofline_share_from_the_trace_pr24_read_by_hand():
    # pretrain_1chip, PR 24: 240 / 120 / 120 calls in 183.29 / 78.54 / 92.71 ms
    cfg, job = load("configs", "cgpt-1p3b-d20"), load("traffic", "pretrain_1chip")
    family = families.load(cfg)
    tr = {"kernel_calls.flash_fwd": 240, "kernel_s.flash_fwd": 0.183291243,
          "kernel_calls.flash_bwd_dq": 120, "kernel_s.flash_bwd_dq": 0.078543877,
          "kernel_calls.flash_bwd_dkv": 120, "kernel_s.flash_bwd_dkv": 0.092712699,
          "kernel_calls.layer_norm_fwd": 3.0, "kernel_s.layer_norm_fwd": 0.001}
    floors = flops.kernel_floors(
        tr, lambda name: family.kernel_counts(name, cfg, job), "TPU v5 lite")
    # FLOPs bound all three: 34.36e9 / 197e12 = 0.17442 ms a forward call
    # against 134.7e6 / 819e9 = 0.16452 ms
    assert floors["kernel_floor_s.flash_fwd"] == pytest.approx(
        240 * 34_359_738_368 / 197e12)
    assert set(floors) == {"kernel_floor_s.flash_fwd",
                           "kernel_floor_s.flash_bwd_dq",
                           "kernel_floor_s.flash_bwd_dkv"}
    obs = {"trace": {**tr, **floors}}
    share = {k: readers.read(f"{k}_roofline_pct.train", obs) for k in BY_HAND}
    assert share["flash_fwd"] == pytest.approx(22.84, abs=0.01)
    assert share["flash_bwd_dq"] == pytest.approx(39.97, abs=0.01)
    assert share["flash_bwd_dkv"] == pytest.approx(45.15, abs=0.01)
    # a trace without the kernel: nothing to read
    assert readers.read("flash_fwd_roofline_pct.train", {"trace": {}}) is None
    with pytest.raises(KeyError):
        flops.kernel_floors(tr, lambda name: (1.0, 1.0), "TPU v9 imaginary")


def test_bytes_bound_a_kernel_with_few_flops():
    floors = flops.kernel_floors({"kernel_calls.k": 2.0},
                                 lambda name: (197e12 * 1e-3, 819e9 * 5e-3),
                                 "TPU v5 lite")
    assert floors == {"kernel_floor_s.k": pytest.approx(2 * 5e-3)}
