"""Packaging test: a built wheel ships the compiled native data plane.

`native/fastdata.cpp` was only compiled for whoever ran
a compiler manually; `pip install .` silently fell back to the Python
parser. The wheel must now contain the `_fastdata` shared object, and the
object must expose the C ABI the ctypes binding drives.
"""

import ctypes
import glob
import os
import subprocess
import sys
import zipfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_wheel_ships_native_parser(tmp_path):
    wheel_dir = tmp_path / "wheels"
    build = subprocess.run(
        [
            sys.executable, "-m", "pip", "wheel", "--no-deps",
            "--no-build-isolation", "-w", str(wheel_dir), REPO,
        ],
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert build.returncode == 0, build.stderr[-2000:]
    wheels = list(wheel_dir.glob("sagemaker_xgboost_container_tpu-*.whl"))
    assert len(wheels) == 1, wheels

    with zipfile.ZipFile(wheels[0]) as zf:
        names = zf.namelist()
        so_names = [
            n for n in names
            if n.startswith("sagemaker_xgboost_container_tpu/_fastdata")
            and n.endswith(".so")
        ]
        assert so_names, f"no _fastdata extension in wheel: {names[:20]}"
        extract_dir = tmp_path / "unpacked"
        zf.extractall(extract_dir)

    # the shipped object must load via ctypes and expose the C ABI
    so_path = str(extract_dir / so_names[0])
    lib = ctypes.CDLL(so_path)
    assert hasattr(lib, "libsvm_count") and hasattr(lib, "libsvm_fill")


def test_resolve_lib_path_branches(tmp_path, monkeypatch):
    """_resolve_lib_path: packaged .so wins in installed layouts (no source,
    or source older); a fresher dev-tree source forces a rebuild."""
    from sagemaker_xgboost_container_tpu.data import native

    fake_so = tmp_path / "_fastdata.cpython-312.so"
    fake_so.write_bytes(b"x")
    fake_src = tmp_path / "fastdata.cpp"

    monkeypatch.setattr(native, "_packaged_extension", lambda: str(fake_so))

    # installed wheel: no source tree at all -> packaged
    monkeypatch.setattr(native, "_SOURCE", str(tmp_path / "missing.cpp"))
    assert native._resolve_lib_path() == ("packaged", str(fake_so))

    # dev tree, source older than the shipped object -> packaged
    fake_src.write_text("// old")
    os.utime(fake_src, (1, 1))
    monkeypatch.setattr(native, "_SOURCE", str(fake_src))
    assert native._resolve_lib_path() == ("packaged", str(fake_so))

    # dev tree, source fresher than the shipped object -> rebuild path
    os.utime(fake_src, None)
    os.utime(fake_so, (1, 1))
    kind, path = native._resolve_lib_path()
    assert kind == "rebuild" and path == native._LIB_PATH

    # no packaged extension at all -> rebuild path
    monkeypatch.setattr(native, "_packaged_extension", lambda: None)
    assert native._resolve_lib_path() == ("rebuild", native._LIB_PATH)
