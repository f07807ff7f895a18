"""The least bytes of one transform+rollup call, counted from shapes."""
from types import SimpleNamespace

from bench import roofline


def test_transform_bytes_by_hand():
    # 256 records, simple model (2 probes), 20 units:
    # block 256*32, probes 2*256*(4+32), facts 256*40, found 256, rollup 400
    assert roofline.transform_bytes(256, 1, 20) == (
        8192 + 18432 + 10240 + 256 + 400)
    # each hop adds one key and one row per record
    assert (roofline.transform_bytes(256, 32, 20)
            - roofline.transform_bytes(256, 1, 20)) == 31 * 256 * 36


def test_share_from_trace():
    run = SimpleNamespace(
        device_trace={"modules": {roofline.PROGRAM: 2e-6}},
        peaks={"hbm_bytes_s": 819e9}, window=(0.0, 10.0),
        transform_calls=[(1.0, 200), (2.0, 100), (11.0, 100)],
        config={"join_depth": 1, "n_units": 20})
    # bytes of the real records only, not of the padded blocks
    want = (roofline.transform_bytes(200, 1, 20)
            + roofline.transform_bytes(100, 1, 20))
    share = roofline.transform_share(run)
    assert abs(share - 100 * want / 819e9 / 2e-6) < 1e-9
    run.device_trace = {"modules": {}}
    assert roofline.transform_share(run) is None
