"""chip_smoke.py cannot pass without a chip — and its own machinery works.

The smoke's generator, loader, query list and comparison run here at a
cut size (8 slices) against the CPU server it starts itself: every
answer must match its reference and every phase must finish, and the
verdict must still be FAIL, naming the backend. The verdict function is
then held to the individual ways a run hides the device.
"""

import copy
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def cpu_run():
    """(report, reasons) of the whole flow on a CPU server, 8 slices."""
    return chip_smoke.run(seed=21, n_slices=8, fail_fast=False)


def test_flow_runs_on_cpu_and_fails_naming_the_backend(cpu_run):
    report, bad = cpu_run
    assert bad, "the smoke must not pass without a chip"
    assert any("backend is 'cpu'" in reason for reason in bad), bad
    assert report["reduced"] == {"slices": [256, 8]}
    # Its own means are sound: data loaded, every class ran, every
    # answer equalled the independent reference.
    assert report["load"]["bits"] == report["referenceBits"] > 5_000_000
    names = [c["name"] for c in report["classes"]]
    assert names == ["count_intersect", "count_union8",
                     "count_difference", "union_materialize", "topn",
                     "topn_src", "count_range", "sum", "fused_tree",
                     "write_then_read"]
    for cls in report["classes"]:
        assert cls["mismatches"] == [], cls["name"]
    assert report["classes"][-1]["setBitResult"] == [True]
    # What the server reports about itself reached the report.
    assert report["build"]["deviceKind"] and report["build"]["deviceCount"]
    assert report["build"]["native"] is True
    assert report["vars"]["deviceFallback"] == 0
    assert {"uploadBps", "packBps", "deviceBps"} <= set(
        report["vars"]["costModel"])
    # Resident slabs are spread over the server's (virtual) devices,
    # so the verdict's sharding check holds here too.
    per = report["vars"]["deviceBlockCache"]["perDeviceBytes"]
    assert len(per) == report["build"]["deviceCount"]
    assert not any("spread evenly" in reason for reason in bad), bad


def test_script_exits_nonzero_without_an_accelerator(tmp_path):
    """The command itself, as the driver runs it: no chip -> fails fast
    after start-up, non-zero, nothing on stdout, backend on stderr."""
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=str(tmp_path))
    assert out.returncode != 0
    assert out.stdout == ""
    assert "backend is 'cpu', not 'tpu'" in out.stderr


def _passing_report() -> dict:
    cls = {"name": "count_intersect", "mismatches": [],
           "devicePrograms": 1, "repeats": [{}, {}]}
    return {
        "build": {"backend": "tpu", "deviceKind": "TPU v5 lite",
                  "deviceCount": 1, "native": True, "nativeExt": True},
        "warmup": {"state": "done", "error": None,
                   "coverage": {"warmed": 8, "programs": 8,
                                "missing": []}},
        "classes": [cls, dict(cls, name="topn")],
        "vars": {"deviceFallback": 0, "costModelVetoes": 3,
                 "costModel": {"syncS": 1e-3},
                 "deviceBlockCache": {"misses": 9, "usedBytes": 1 << 20,
                                      "perDeviceBytes": {"0": 1 << 20}}},
        "compileCache": {"firstCalls": 12, "persistentCacheDir": "/c",
                         "persistentHits": 0, "persistentMisses": 12},
    }


def test_verdict_accepts_a_clean_tpu_report():
    assert chip_smoke.verdict(_passing_report()) == []


@pytest.mark.parametrize("mutate, needle", [
    (lambda r: r["vars"].update(deviceFallback=2), "deviceFallback"),
    (lambda r: r["classes"][1].update(devicePrograms=0),
     "topn: served by the host"),
    (lambda r: r["warmup"].update(state="failed", error="boom"),
     "warmup.state is 'failed'"),
    (lambda r: r["warmup"]["coverage"].update(warmed=7,
                                              missing=["fused_tree"]),
     "warmup coverage 7/8"),
    (lambda r: r["build"].update(backend="cpu"), "backend is 'cpu'"),
    (lambda r: r["build"].update(nativeExt=False), "nativeExt"),
    (lambda r: r["classes"][0]["mismatches"].append(
        {"repeat": 0, "got": "[1]", "want": "[2]"}), "got [1], want [2]"),
    (lambda r: r["compileCache"].update(firstCalls=0), "firstCalls"),
    (lambda r: r["compileCache"].update(persistentMisses=0),
     "neither hit nor miss"),
    (lambda r: r["vars"]["deviceBlockCache"].update(usedBytes=0),
     "residency cache never filled"),
    (lambda r: r["vars"].pop("costModel"), "never calibrated"),
    # four devices reported, every slab on device 0
    (lambda r: r["build"].update(deviceCount=4), "not spread evenly"),
    # ... or one device holding more than its share
    (lambda r: (r["build"].update(deviceCount=2),
                r["vars"]["deviceBlockCache"].update(perDeviceBytes={
                    "0": 3 << 18, "1": 1 << 18})), "not spread evenly"),
    # ... or every device holding a whole copy
    (lambda r: (r["build"].update(deviceCount=2),
                r["vars"]["deviceBlockCache"].update(perDeviceBytes={
                    "0": 1 << 20, "1": 1 << 20})), "not spread evenly"),
])
def test_verdict_rejects(mutate, needle):
    report = copy.deepcopy(_passing_report())
    mutate(report)
    bad = chip_smoke.verdict(report)
    assert any(needle in reason for reason in bad), bad
