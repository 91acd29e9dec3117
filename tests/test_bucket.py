"""The power-of-two ladder the serving bucketer and the decode engine's
prefill buckets share (``paddle_tpu/bucket.py``)."""

from paddle_tpu import bucket as tb


def test_bucket_dim_edges():
    assert tb.bucket_dim(0) == 1
    assert tb.bucket_dim(1) == 1
    assert tb.bucket_dim(2) == 2
    assert tb.bucket_dim(3) == 4
    assert tb.bucket_dim(4) == 4
    assert tb.bucket_dim(5) == 8
    assert tb.bucket_dim(8) == 8
    assert tb.bucket_dim(9) == 16
    assert tb.bucket_dim(1 << 20) == 1 << 20
    assert tb.bucket_dim((1 << 20) + 1) == 1 << 21


def test_bucket_ladder():
    assert tb.bucket_ladder(1) == (1,)
    assert tb.bucket_ladder(5) == (1, 2, 4, 8)
    assert tb.bucket_ladder(8) == (1, 2, 4, 8)


def test_serving_bucketer_delegates():
    from paddle_tpu.serving import batching

    for n in (1, 2, 3, 7, 8, 9, 100):
        assert batching.next_bucket(n) == tb.bucket_dim(n)
    assert batching.bucket_ladder(6) == tb.bucket_ladder(6)
