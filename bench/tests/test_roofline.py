import pytest

from roofline import bandwidth_share_pct, message_words, probe_least_bytes


def test_hand_worked_job():
    # one key column: 3 words a message.  1000 valid received rows (probe
    # and build), 300 of the probe rows hit: 1000*3*4 + 300*4 bytes
    job = {"recv_fwd": 1000, "hits": 300, "key_width": 1}
    assert probe_least_bytes(job) == 13200
    # two key columns: the fingerprint is a hash, keys ride along: 5 words
    assert message_words(2) == 5
    assert probe_least_bytes({"recv_fwd": 10, "hits": 0, "key_width": 2}) == 200


def test_share_of_v5e_bandwidth():
    # 819 MB in 1 ms at 819 GB/s is the whole roofline
    assert bandwidth_share_pct(819e6, 1e-3, "TPU v5 lite") == pytest.approx(100.0)
    assert bandwidth_share_pct(0, 1.0, "TPU v5 lite") is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        bandwidth_share_pct(1.0, 1.0, "TPU v99")
