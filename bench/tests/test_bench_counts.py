"""bench/counts.py against counts made by hand for one small shape."""
import pytest

from bench import counts


def test_mf_step_flops_small_shape():
    # C=3, I=2, nnz=5, k=4, k_b=2:
    # Grams 2·16·5 = 160; explicit parts 2·2·8·5 = 160; R' 2·2·4·5 = 80
    assert counts.mf_step_flops(3, 2, 5, 4, 2) == 160 + 160 + 80


def test_mf_step_flops_paper_size():
    # the section-6 step: 8.8 + 2.6 + 0.55 GFLOP
    f = counts.mf_step_flops(200_000, 68_000, 20_000_000, 128, 8)
    assert f == 2 * 128**2 * 268_000 + 16 * 8 * 20_000_000 + 16 * 128 * 268_000


def test_topk_score_work_small_shape():
    # b=2 rows, N=10 items, D=4, K=3, L=5
    flops, nbytes = counts.topk_score_work(2, 10, 4, 3, 5)
    assert flops == 2 * 2 * 10 * 4
    assert nbytes == 4 * (10 * 4 + 2 * 4 + 2 * 5 + 2 * 2 * 3)


def test_least_time_names_its_bound():
    peak = counts.peaks("TPU v5 lite")
    t, bound = counts.least_time(1.0, 819e9, peak)
    assert bound == "bytes" and t == pytest.approx(1.0)
    t, bound = counts.least_time(197e12 * 2, 1.0, peak)
    assert bound == "flops" and t == pytest.approx(2.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v99")
