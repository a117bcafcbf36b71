"""The native loader only ever loads a binary built from the committed
C source as it is now."""

import os
import shutil
from pathlib import Path

import pytest

import traceml_tpu.native as native


@pytest.fixture
def native_dir(tmp_path, monkeypatch):
    if shutil.which(os.environ.get("CC", "cc")) is None:
        pytest.skip("no C compiler")
    src = Path(native.__file__).parent / "ring.c"
    (tmp_path / "ring.c").write_text(src.read_text())
    monkeypatch.setattr(native, "_HERE", tmp_path)
    monkeypatch.setattr(native, "_cached", {})
    monkeypatch.delenv("TRACEML_NO_NATIVE", raising=False)
    return tmp_path


def test_edited_source_builds_anew_and_never_loads_an_old_binary(native_dir):
    # a binary of unknown provenance, as an untracked checkout may hold
    leftover = native_dir / "_ring.cpython-312-x86_64-linux-gnu.so"
    leftover.write_bytes(b"not built from this source")
    first = native.get_ring()
    assert first is not None
    first_path = native._artifact("ring.c", "_ring")
    assert Path(first.__file__) == first_path
    assert not leftover.exists()

    with open(native_dir / "ring.c", "a") as fh:
        fh.write("\n/* edited */\n")
    native._cached.clear()
    second = native.get_ring()
    second_path = native._artifact("ring.c", "_ring")
    assert second_path != first_path
    assert Path(second.__file__) == second_path
    assert not first_path.exists()
    assert sorted(p.name for p in native_dir.glob("*.so")) == [second_path.name]


def test_missing_source_loads_nothing(native_dir):
    (native_dir / "ring.c").unlink()
    (native_dir / "_ring.cpython-312-x86_64-linux-gnu.so").write_bytes(b"x")
    assert native.get_ring() is None
