"""The step's least bytes per point-cycle against hand-counted 16-PE
fabrics, and the peaks table."""
import pytest

from ringbench import peaks, roofline
from ringbench.reference import fabric

BASE = {"n_pes": 16, "queue_depth": 2, "src_queue_depth": 8}


def test_ring_mesh_16_bytes():
    f = fabric.build({**BASE, "family": "ring_mesh", "blocks_x": 1,
                      "blocks_y": 1})
    # 16 inject buffers of 8, 16 eject sinks (no slots), 16 x 2
    # directions x 2 VCs of ring buffers of 2, 4 + 4 master<->router
    # buffers of 2: 104 queues, 128 + 128 + 16 = 272 slots.
    assert f.n_queues == 104
    state = 4 * (272 + 2 * 104)
    assert roofline.bytes_per_point_cycle(f) == 2 * state + 3 * 16 == 3888


def test_flat_mesh_16_bytes():
    f = fabric.build({**BASE, "family": "flat_mesh", "blocks_x": 4,
                      "blocks_y": 4})
    # 16 inject buffers of 8, 16 eject sinks, 48 directed mesh channels
    # x 2 VCs of 2: 128 queues, 128 + 192 = 320 slots.
    assert f.n_queues == 128
    state = 4 * (320 + 2 * 128)
    assert roofline.bytes_per_point_cycle(f) == 2 * state + 3 * 16 == 4656


def test_peaks_are_published_and_unknown_kinds_refused():
    assert peaks.of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.of("cpu")
