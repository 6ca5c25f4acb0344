"""Event-model direct unit tests (reference role:
TEST/managment/EventTestCase.java:42 — converters/positions exercised
without a full app)."""
import numpy as np
import pytest

from siddhi_tpu.core import event as ev
from siddhi_tpu.query_api.definition import StreamDefinition


def _schema(*attrs):
    sdef = StreamDefinition("S")
    for n, t in attrs:
        sdef.attribute(n, t)
    return ev.Schema(sdef, ev.StringInterner())


def test_bucket_size_ladder():
    assert ev.bucket_size(1) == 8
    assert ev.bucket_size(8) == 8
    assert ev.bucket_size(9) == 32
    assert ev.bucket_size(524288) == 524288
    assert ev.bucket_size(524289) == 1048576
    with pytest.raises(ValueError):
        ev.bucket_size(3_000_000)


def test_pack_unpack_roundtrip_all_types():
    schema = _schema(("s", "string"), ("i", "int"), ("l", "long"),
                     ("f", "float"), ("d", "double"), ("b", "bool"))
    events = [ev.Event(1000 + k, [f"v{k}", k, k * 10, k + 0.5, k + 0.25,
                                  k % 2 == 0]) for k in range(5)]
    staged = ev.pack_np(schema, events)
    assert staged.n == 5
    batch = staged.to_device(schema)
    out = ev.unpack(schema, batch)
    assert len(out) == 5
    for k, (kind, e) in enumerate(out):
        assert kind == ev.CURRENT
        assert e.timestamp == 1000 + k
        assert e.data[0] == f"v{k}"
        assert e.data[1] == k and e.data[2] == k * 10
        assert e.data[3] == pytest.approx(k + 0.5)
        assert e.data[5] == (k % 2 == 0)


def test_unpack_filters_kinds():
    schema = _schema(("v", "int"))
    cap = 8
    ts = np.arange(cap, dtype=np.int64)
    kind = np.array([ev.CURRENT, ev.EXPIRED, ev.TIMER, ev.RESET] * 2,
                    np.int32)
    valid = np.ones(cap, bool)
    cols = (np.arange(cap, dtype=np.int32),)
    import jax.numpy as jnp
    batch = ev.EventBatch(jnp.asarray(ts), jnp.asarray(kind),
                          jnp.asarray(valid), (jnp.asarray(cols[0]),))
    cur = ev.unpack(schema, batch, want_kinds=(ev.CURRENT,))
    assert [e.data[0] for _, e in cur] == [0, 4]
    both = ev.unpack(schema, batch, want_kinds=(ev.CURRENT, ev.EXPIRED))
    assert [k for k, _ in both] == [ev.CURRENT, ev.EXPIRED] * 2
    # TIMER/RESET rows never surface as events
    alln = ev.unpack(schema, batch, want_kinds=None)
    assert all(k in (ev.CURRENT, ev.EXPIRED) for k, _ in alln)


def test_interner_identity_and_null():
    interner = ev.StringInterner()
    a = interner.intern("hello")
    b = interner.intern("hello")
    assert a == b
    assert interner.lookup(a) == "hello"
    assert interner.lookup(ev.NULL_ID) is None
    c = interner.intern("world")
    assert c != a


def test_string_null_and_uuid_sentinel_decode():
    schema = _schema(("s", "string"))
    assert schema.decode_value("STRING", ev.NULL_ID) is None
    u1 = schema.decode_value("STRING", ev.UUID_SENTINEL)
    u2 = schema.decode_value("STRING", ev.UUID_SENTINEL)
    assert u1 != u2 and len(u1) == 36


def test_encode_value_types():
    schema = _schema(("s", "string"), ("i", "int"), ("b", "bool"))
    # null -> in-band null value, round-tripping back to None
    assert schema.encode_value("INT", None) == ev.NULL_INT
    assert schema.decode_value("INT", ev.NULL_INT) is None
    assert schema.encode_value("BOOL", 1) is True
    sid = schema.encode_value("STRING", "x")
    assert schema.decode_value("STRING", sid) == "x"


def test_staged_batch_padding():
    schema = _schema(("v", "int"))
    events = [ev.Event(1, [7])] * 3
    staged = ev.pack_np(schema, events)
    cap = ev.bucket_size(3)
    assert staged.valid.shape[0] == cap
    assert staged.valid[:3].all() and not staged.valid[3:].any()


T0 = 1_760_000_000_000      # epoch milliseconds
TS_WIRE = {
    # name: (real rows' timestamps, the delta's dtype)
    "same_ms": ([T0] * 5, np.int32),
    "ascending": ([T0, T0 + 1, T0 + 7, T0 + 60_000], np.int32),
    "negative_deltas": ([T0, T0 - 3, T0 + 2, T0 - 86_400_000], np.int32),
    "full_bucket": (list(T0 + np.arange(8)), np.int32),
    "span_2_31_minus_1": ([T0, T0 + 2**31 - 1], np.int32),
    "span_2_31": ([T0, T0 + 2**31], np.int64),
    "back_2_31": ([T0, T0 - 2**31], np.int32),
    "back_2_31_plus_1": ([T0, T0 - 2**31 - 1], np.int64),
    "epoch_zero_and_now": ([0, T0], np.int64),
    "empty": ([], np.int32),
}


@pytest.mark.parametrize("name", sorted(TS_WIRE))
def test_ts_wire_round_trip(name):
    """`encode_ts` -> `decode_ts` is the staged column on every real row,
    padding decodes to the base, and the delta narrows exactly when every
    real row's distance from the first fits int32."""
    real, dtype = TS_WIRE[name]
    n = len(real)
    ts = np.zeros(ev.bucket_size(max(n, 1)), np.int64)   # zero padding
    ts[:n] = real
    base, delta = ev.encode_ts(ts, n)
    assert delta.dtype == dtype and delta.shape == ts.shape
    assert np.asarray(base).dtype == np.int64 and np.ndim(base) == 0
    assert not delta[n:].any()
    back = np.asarray(ev.decode_ts(base, delta))
    assert back.dtype == np.int64
    np.testing.assert_array_equal(back[:n], ts[:n])
    np.testing.assert_array_equal(back[n:], int(base))
    assert int(base) == (real[0] if n else 0)
