"""Operation and byte counts at the paper's shapes, against numbers
worked out by hand, and the roofline share and peak table."""
import pytest

from perfbench.harness import counts, peaks

PAPER = dict(b=32, l=100, p=750_000, c=1024, n_probe=8, k=256, s=1000)


def test_ivf_probe_bytes_count_the_mean_list_not_the_padded_capacity():
    # 1024*100*4 + 32*100*4 + 32*8*(750000/1024)*(100*4 + 4) + 32*256*8
    want = 409_600 + 12_800 + 75_750_000 + 65_536
    got = counts.ivf_probe_bytes(32, 100, 750_000, 1024, 8, 256)
    assert got == pytest.approx(want)


def test_ivf_probe_flops():
    # 2*32*1024*100 + 2*32*8*(750000/1024)*100
    assert counts.ivf_probe_flops(32, 100, 750_000, 1024, 8) == pytest.approx(
        6_553_600 + 37_500_000)


def test_covgrad_bytes_and_flops():
    # forward 12.8e6 rows + 32000*12 + 12800 + 128000; backward 12.8e6 + 32000*8 + 12800
    assert counts.covgrad_bytes(32, 1000, 100) == pytest.approx(13_324_800 + 13_068_800)
    assert counts.covgrad_flops(32, 1000, 100) == pytest.approx(12_800_000)


@pytest.mark.parametrize("retrieval,want", [(True, 58_133_600), (False, 14_080_000)])
def test_fopo_step_flops(retrieval, want):
    assert counts.fopo_step_flops(32, 100, 750_000, 1000, 1024, 8, retrieval) == \
        pytest.approx(want)


def test_sasrec_tower_flops():
    # per block: qkv 6e6, attention 4e6, feed-forward 4e6; two blocks
    assert counts.sasrec_tower_flops(8, 50, 50, 2) == pytest.approx(28_000_000)


def test_roofline_share_takes_the_binding_bound():
    pk = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_share(50.0, 20.0, 4.0, pk) == pytest.approx(50.0)
    assert counts.roofline_share(400.0, 20.0, 8.0, pk) == pytest.approx(50.0)
    assert counts.roofline_share(1.0, 1.0, 0.0, pk) is None


def test_peak_table_knows_the_v5e_and_refuses_others():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
