"""The kernels' work count and the table of peaks."""

import pytest

from bench import kernel_work, peaks


@pytest.mark.parametrize("lanes,pages", [(4, 8832), (6, 3072)])
def test_work_matches_hand_counts(lanes, pages):
    # batched_evict: key f32 + sizes f32 + evictable bool in, mask bool
    # out per page; need_free in
    assert kernel_work.work("batched_evict", lanes, pages)["bytes"] \
        == lanes * (10 * pages + 4)
    # fifo_grant: key i32 + sizes f32 in, mask bool out per page; budget,
    # pops in, granted bytes and count out
    assert kernel_work.work("fifo_grant", lanes, pages)["bytes"] \
        == lanes * (9 * pages + 16)
    # wake_solve: key i32 + sizes f32 in, grant step i32 out per page;
    # credit0, inc, pops in
    assert kernel_work.work("wake_solve", lanes, pages)["bytes"] \
        == lanes * (12 * pages + 12)
    assert kernel_work.work("fifo_grant", lanes, pages)["ops"] \
        == lanes * pages * 2


def test_least_time_is_bound_by_bytes_on_v5e():
    peak = peaks.peaks("TPU v5 lite")
    t = kernel_work.least_seconds("batched_evict", 4, 8832, peak)
    assert t == pytest.approx(4 * (10 * 8832 + 4) / 819e9)


def test_v5e_peaks_and_unknown_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("cpu")
