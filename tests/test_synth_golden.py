"""Golden regression for the synthesis sweep: the resource vectors every
downstream model is fitted on must not drift silently when kernels or the
hloscan census change.  If a change is *intentional*, bump
``synth.SWEEP_SCHEMA_VERSION`` and regenerate the fixture:

    PYTHONPATH=src python tests/test_synth_golden.py
"""

import json
from pathlib import Path

import pytest

from repro.configs.paper_conv import SWEEP, ConvSweepConfig
from repro.core import synth

GOLDEN = Path(__file__).parent / "golden" / "synth_golden.json"


# the pinned design points: conv1 (no MXU) and conv3 (packed, int8
# unpacked, int16 unpacked) on both sides of the container boundary
GOLDEN_POINTS = [("conv1", 4, 4), ("conv1", 8, 8), ("conv1", 12, 10),
                 ("conv3", 4, 4), ("conv3", 8, 8), ("conv3", 12, 10)]


def _golden():
    return json.loads(GOLDEN.read_text())


def test_golden_fixture_matches_schema_version():
    assert _golden()["version"] == synth.SWEEP_SCHEMA_VERSION, (
        "SWEEP_SCHEMA_VERSION changed — regenerate the golden fixture "
        "to match the new row semantics")


@pytest.mark.parametrize("i", range(len(GOLDEN_POINTS)),
                         ids=lambda i: f"row{i}")
def test_synth_traces_match_golden(i):
    row = _golden()["rows"][i]
    got = synth.synth_one(row["block"], row["data_bits"], row["coeff_bits"],
                          SWEEP)
    for key, want in row.items():
        if key in ("block", "data_bits", "coeff_bits"):
            continue
        assert got[key] == pytest.approx(want, rel=1e-6), (
            row["block"], row["data_bits"], row["coeff_bits"], key)


# ---------------------------------------------------------------------------
# SWEEP_SCHEMA_VERSION cache regeneration
# ---------------------------------------------------------------------------

TINY = ConvSweepConfig(name="tiny", blocks=("conv1",),
                       data_bits=(4,), coeff_bits=(4,))


def test_stale_cache_regenerates(tmp_path):
    cache = tmp_path / "synth.json"
    stale = [{"block": "conv1", "data_bits": 4, "coeff_bits": 4,
              "vpu_ops": -1.0}]
    # pre-versioning bare-list payload → regenerated
    cache.write_text(json.dumps(stale))
    rows = synth.run_sweep(TINY, cache_path=cache)
    assert rows[0]["vpu_ops"] > 0
    payload = json.loads(cache.read_text())
    assert payload["version"] == synth.SWEEP_SCHEMA_VERSION

    # wrong version number → regenerated too
    cache.write_text(json.dumps({"version": synth.SWEEP_SCHEMA_VERSION - 1,
                                 "rows": stale}))
    rows = synth.run_sweep(TINY, cache_path=cache)
    assert rows[0]["vpu_ops"] > 0

    # current version and sources → served verbatim, no re-trace
    sentinel = [{"block": "conv1", "data_bits": 4, "coeff_bits": 4,
                 "vpu_ops": 123.0}]
    cache.write_text(json.dumps({"version": synth.SWEEP_SCHEMA_VERSION,
                                 "sources": synth.sources_digest(),
                                 "rows": sentinel}))
    assert synth.run_sweep(TINY, cache_path=cache) == sentinel

    # force=True ignores even a current cache
    rows = synth.run_sweep(TINY, cache_path=cache, force=True)
    assert rows[0]["vpu_ops"] > 0


def test_cache_from_other_sources_regenerates(tmp_path, monkeypatch):
    """A cache written under another digest of the traced sources (an
    edited kernel, block or census) is re-swept, never served."""
    cache = tmp_path / "synth.json"
    sentinel = [{"block": "conv1", "data_bits": 4, "coeff_bits": 4,
                 "vpu_ops": 123.0}]
    cache.write_text(json.dumps({"version": synth.SWEEP_SCHEMA_VERSION,
                                 "sources": synth.sources_digest(),
                                 "rows": sentinel}))
    assert synth.run_sweep(TINY, cache_path=cache) == sentinel

    monkeypatch.setattr(synth, "sources_digest", lambda: "edited-kernel")
    rows = synth.run_sweep(TINY, cache_path=cache)
    assert rows != sentinel and rows[0]["vpu_ops"] > 0
    assert json.loads(cache.read_text())["sources"] == "edited-kernel"


def test_sources_digest_covers_the_kernels():
    """The digest reads the kernel bodies, the blocks and the census."""
    files = {p.relative_to(synth._PKG).as_posix()
             for pattern in synth.TRACED_SOURCES
             for p in synth._PKG.glob(pattern)}
    assert {"kernels/conv2d.py", "blocks/paper.py", "blocks/base.py",
            "core/hloscan.py", "core/synth.py"} <= files
    assert len(synth.sources_digest()) == 64


if __name__ == "__main__":                  # regenerate the fixture
    rows = [dict(block=b, data_bits=d, coeff_bits=c,
                 **synth.synth_one(b, d, c, SWEEP))
            for b, d, c in GOLDEN_POINTS]
    GOLDEN.write_text(json.dumps({"version": synth.SWEEP_SCHEMA_VERSION,
                                  "rows": rows}, indent=1, sort_keys=True)
                      + "\n")
