"""Least bytes of an iteration, roofline share and the peaks table."""
import pytest

from bench import peaks, roofline


def test_iteration_bytes_by_hand():
    # kron20 as generated from one seed: 16,085,108 edges, 2^20 vertices, kappa 16
    edges, v, k = 16_085_108, 1 << 20, 16
    assert roofline.iteration_bytes(v, edges, k) == \
        edges * (4 + 4 + 4) + v * k * 4 + v * k * 4
    assert roofline.iteration_bytes(v, edges, k) == 327_239_024


def test_roofline_pct_is_least_time_over_time_taken():
    # 819 MB at 819 GB/s is 1 ms; taking 4 ms is 25% of the bound
    assert roofline.roofline_pct(819e6, 4e-3, 819e9) == pytest.approx(25.0)


def test_iteration_roofline_pct_reads_the_trace_summary():
    class S:
        waves, iteration_s = 2, 2 * 10 * 1e-3

    class Ctx:
        summary, iterations, peaks = S, 10, {"hbm_bytes_per_s": 819e9}
        num_vertices, num_edges, edge_shards, kappa = 1000, 10_000, 1, 16

    least = 12 * 10_000 + 8 * 1000 * 16
    assert roofline.iteration_roofline_pct(Ctx) == \
        pytest.approx(100 * least / 819e9 / 1e-3)
    Ctx.summary = None
    assert roofline.iteration_roofline_pct(Ctx) is None


def test_iteration_roofline_pct_of_a_split_stream_counts_every_shard():
    # four devices each stream a quarter of the edges in the same device
    # time per iteration as one device streaming all: a quarter of the share
    class S:
        waves, iteration_s = 2, 2 * 10 * 1e-3

    class Ctx:
        summary, iterations, peaks = S, 10, {"hbm_bytes_per_s": 819e9}
        num_vertices, num_edges, edge_shards, kappa = 1000, 10_000, 1, 16

    one = roofline.iteration_roofline_pct(Ctx)
    Ctx.edge_shards = 4
    assert roofline.iteration_roofline_pct(Ctx) == pytest.approx(one / 4)


def test_peaks_of_v5e_cite_their_source():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert "Google Cloud" in peaks.__doc__


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5"])
def test_unknown_device_has_no_peaks(kind):
    with pytest.raises(KeyError):
        peaks.peaks(kind)
