"""`_private/serialization.py`: which arrays ride out of band."""

import numpy as np
import pytest

from ray_tpu._private import serialization


def _round_trip(value):
    header, buffers = serialization.serialize(value)
    return serialization.deserialize(
        header + b"".join(bytes(b) for b in buffers)), header, buffers


@pytest.mark.parametrize("cut", ["minor", "rows", "step"])
def test_a_strided_array_goes_out_of_band_as_one_dense_copy(cut):
    """numpy pickles an array that is contiguous in neither order
    in-band, copy after copy (a snapshot leaf whose minor dimension the
    device pads reaches the host as such a view of the padded rows). The
    pickler makes it dense once; the value is what it was."""
    padded = np.arange(4 * 6 * 16, dtype=np.float32).reshape(4, 6, 16)
    strided = {"minor": padded[:, :, :13], "rows": padded[:, 1:4],
               "step": padded[::2, :, ::3]}[cut]
    assert not (strided.flags.c_contiguous or strided.flags.f_contiguous)
    out, header, buffers = _round_trip({"leaf": strided})
    assert [b.nbytes for b in buffers] == [strided.nbytes]
    assert len(header) < 400          # nothing of the array in the stream
    assert out["leaf"].flags.c_contiguous
    assert out["leaf"].dtype == strided.dtype
    assert np.array_equal(out["leaf"], strided)


def test_contiguous_and_object_arrays_are_pickled_as_before():
    padded = np.arange(4 * 6 * 16, dtype=np.float32).reshape(4, 6, 16)
    objects = np.array([1, "a", None, 2.5], dtype=object)[::2]
    out, _, buffers = _round_trip(
        {"c": padded[1], "f": padded.T, "objects": objects})
    # a view that is dense in either order goes out as it is: no copy
    assert [b.nbytes for b in buffers] == [padded[1].nbytes, padded.nbytes]
    assert np.array_equal(out["c"], padded[1])
    assert np.array_equal(out["f"], padded.T) and out["f"].flags.f_contiguous
    assert list(out["objects"]) == [1, None]
