"""CPU tests of the chip benchmark's arithmetic and of one whole run at a
tiny size.  Run from the checkout: ``python -m pytest benchmarks/chip/tests``."""

import json
import os
import pathlib
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
for p in (CHIP, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import pytest  # noqa: E402

TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=512,
            serving={"max_batch": 4, "max_len": 64})


def make_root(tmp: pathlib.Path, **sizes) -> pathlib.Path:
    """A checkout-like directory whose BENCHMARK.json points the cells at a
    small copy of internlm2-1.8b (same file, smaller sizes)."""
    (tmp / "cfg").mkdir(parents=True, exist_ok=True)
    cfg = json.loads((CHIP / "configs" / "internlm2-1.8b.json").read_text())
    cfg.update(sizes)
    (tmp / "cfg" / "small.json").write_text(json.dumps(cfg))
    shutil.copy(CHIP / "configs" / "internlm2-1.8b.py", tmp / "cfg" / "small.py")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"][0]["file"] = "cfg/small.json"
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path, **TINY)


@pytest.fixture
def cpu_peaks(monkeypatch):
    """The CPU has no published peaks; the tests borrow the v5e's."""
    import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
