"""launch/hlo_stats.py unit coverage.

Two layers, all fast:

  * the pure shape-string helpers on synthetic inputs,
  * ``analyze_module`` round-tripped against REAL compiled HLO (CPU) where
    the expected flops are known in closed form, plus a synthetic module
    exercising the collective link-bytes model and the cross-pod split.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_stats as H


class TestShapeHelpers:
    def test_shape_bytes(self):
        assert H.shape_bytes("f32[8,4]{1,0}") == 8 * 4 * 4
        assert H.shape_bytes("bf16[2,3]") == 12
        assert H.shape_bytes("f32[]") == 4
        # tuples sum every array inside
        assert H.shape_bytes("(f32[8]{0}, u32[4])") == 32 + 16
        # unknown dtype tokens are skipped, not crashed on
        assert H.shape_bytes("token[8]") == 0

    def test_shape_dims_and_elems(self):
        assert H.shape_dims("f32[8,4]{1,0}") == [8, 4]
        assert H.shape_dims("f32[]") == []
        assert H.shape_dims("no arrays here") == []
        assert H.shape_elems("f32[8,4]") == 32
        assert H.shape_elems("f32[]") == 1

    def test_last_array_bytes(self):
        # async -start result buffers: the LAST array of the tuple shape
        assert H.last_array_bytes("(f32[8]{0}, u32[], f32[128]{0})") == 512
        assert H.last_array_bytes("f32[16]") == 64
        assert H.last_array_bytes("nothing") == 0


class TestAnalyzeModuleRoundTrip:
    """Feed analyze_module REAL optimized HLO with a known cost."""

    def test_dot_flops_exact(self):
        m, k, n = 48, 96, 32
        sds = jax.ShapeDtypeStruct
        hlo = (
            jax.jit(lambda a, b: a @ b)
            .lower(sds((m, k), jnp.float32), sds((k, n), jnp.float32))
            .compile()
            .as_text()
        )
        costs = H.analyze_module(hlo, 1)
        assert costs.flops == 2.0 * m * n * k
        # HBM model must at least cover the dot's operands + output
        assert costs.bytes >= 4 * (m * k + k * n + m * n)
        assert costs.link_bytes == 0.0 and costs.collectives == {}

    def test_scan_body_scales_by_trip_count(self):
        trips, d = 7, 16

        def g(x):
            return jax.lax.scan(lambda c, _: (c @ c, None), x, None, length=trips)[0]

        hlo = (
            jax.jit(g)
            .lower(jax.ShapeDtypeStruct((d, d), jnp.float32))
            .compile()
            .as_text()
        )
        assert H.while_trip_counts(hlo) == [trips]
        # compiled.cost_analysis() counts the body once — the text walk
        # must multiply it out (this is hlo_stats' reason to exist)
        assert H.analyze_module(hlo, 1).flops == trips * 2.0 * d * d * d


_SYNTH_HLO = """\
HloModule synth

ENTRY %main (p0: f32[64,32]) -> f32[64,32] {
  %p0 = f32[64,32]{1,0} parameter(0)
  %ar = f32[64,32]{1,0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %t = f32[64,32]{1,0} tanh(%ar)
}
"""


class TestSyntheticCollectives:
    def test_all_reduce_ring_bytes(self):
        payload = 64 * 32 * 4
        costs = H.analyze_module(_SYNTH_HLO, 4)
        # ring all-reduce: 2 · payload · (g−1)/g
        assert costs.link_bytes == pytest.approx(2.0 * payload * 3 / 4)
        assert costs.xpod_bytes == 0.0
        (key,) = costs.collectives
        assert key == "all-reduce"
        assert costs.collectives[key]["count"] == 1.0
        assert costs.collectives[key]["payload_bytes"] == payload

    def test_cross_pod_split(self):
        # group {0,1,2,3} spans two pods of size 2 → link moves to DCI
        costs = H.analyze_module(_SYNTH_HLO, 4, pod_size=2)
        assert costs.link_bytes == 0.0
        assert costs.xpod_bytes > 0.0
        assert list(costs.collectives) == ["all-reduce/xpod"]


class TestRoofline:
    def test_dominant_term_and_fraction(self):
        r = H.roofline_terms(
            flops=H.PEAK_FLOPS, hbm_bytes=2.0 * H.HBM_BW, link_bytes=0.0
        )
        assert r["compute_s"] == pytest.approx(1.0)
        assert r["memory_s"] == pytest.approx(2.0)
        assert r["dominant"] == "memory"
        assert r["bound_s"] == pytest.approx(2.0)
        assert r["roofline_fraction"] == pytest.approx(0.5)

    def test_cross_pod_bytes_ride_dci(self):
        r = H.roofline_terms(
            flops=0.0, hbm_bytes=0.0, link_bytes=H.ICI_BW, xpod_bytes=H.DCI_BW
        )
        assert r["dominant"] == "collective"
        assert r["collective_s"] == pytest.approx(2.0)

    def test_zero_is_well_defined(self):
        r = H.roofline_terms(flops=0.0, hbm_bytes=0.0, link_bytes=0.0)
        assert r["bound_s"] == 0.0 and r["roofline_fraction"] == 0.0
