"""The native helpers build from the committed ``.c`` files alone: a
fresh checkout has no ``.so`` (git-ignored), so first use compiles, and
the library's name records the hash of the sources it was built from."""
import ctypes
import os
import shutil

import pytest

from lightning_tpu.utils import native


@pytest.fixture
def clean_copy(tmp_path):
    for name in native._SOURCES:
        shutil.copy(os.path.join(native._SRC_DIR, name), tmp_path / name)
    return tmp_path


def test_builds_from_a_clean_copy_of_the_sources(clean_copy):
    assert not list(clean_copy.glob("*.so"))
    path = native._build(str(clean_copy))
    assert os.path.dirname(path) == str(clean_copy)
    lib = ctypes.CDLL(path)
    lib.crc32c.restype = ctypes.c_uint32
    lib.crc32c.argtypes = [ctypes.c_uint32, ctypes.c_char_p, ctypes.c_size_t]
    # the CRC-32C check value (RFC 3720 appendix B.4)
    assert lib.crc32c(0, b"123456789", 9) == 0xE3069283
    # same sources, same name: the second call compiles nothing
    mtime = os.path.getmtime(path)
    assert native._build(str(clean_copy)) == path
    assert os.path.getmtime(path) == mtime


def test_edited_source_rebuilds_under_a_new_name(clean_copy):
    first = native._build(str(clean_copy))
    with open(clean_copy / native._SOURCES[0], "a") as f:
        f.write("\n/* edited */\n")
    second = native._build(str(clean_copy))
    assert second != first
    # the stale library goes; one build per source state is kept
    assert [p.name for p in clean_copy.glob("*.so")] == [
        os.path.basename(second)]


def test_build_failure_raises_with_the_compilers_stderr(clean_copy):
    with open(clean_copy / native._SOURCES[0], "a") as f:
        f.write("\nthis is not C;\n")
    with pytest.raises(native.NativeBuildError) as ei:
        native._build(str(clean_copy))
    assert "error" in str(ei.value)
    assert not list(clean_copy.glob("*.so"))
    assert not list(clean_copy.glob("*.tmp"))
