"""Inter-party wire decoders + the process-separated leader/helper.

The decoders must invert the conformance-locked encoders for every
instantiation; the subprocess demo must reproduce a conformance
vector's aggregate shares byte for byte with leader and helper as
separate OS processes exchanging only wire bytes (the wire types of
the reference implementation's poc/mastic.py:31-49).
"""

import json
import os

import pytest

pytestmark = pytest.mark.slow


from mastic_tpu import wire
from mastic_tpu.common import gen_rand
from mastic_tpu.mastic import (MasticCount, MasticHistogram, MasticSum,
                               MasticSumVec)
from mastic_tpu.testvec_codec import (encode_agg_share,
                                      encode_input_share,
                                      encode_prep_share)

TEST_VEC_DIR = os.environ.get(
    "MASTIC_TEST_VEC", "/root/reference/test_vec/mastic")

INSTANCES = [
    (MasticCount(2), (True, False), 1),
    (MasticSum(2, 7), (False, True), 5),
    (MasticSumVec(4, 3, 1, 1), (True, False, True, True), [1, 0, 1]),
    (MasticHistogram(2, 4, 2), (False, False), 3),
]


@pytest.mark.parametrize("case", INSTANCES,
                         ids=[type(m).__name__ for (m, _, _) in INSTANCES])
def test_wire_roundtrip(case) -> None:
    (m, alpha, weight) = case
    ctx = b"wire test"
    nonce = gen_rand(m.NONCE_SIZE)
    rand = gen_rand(m.RAND_SIZE)
    (public_share, input_shares) = m.shard(ctx, (alpha, weight), nonce,
                                           rand)
    for agg_id in range(2):
        blob = encode_input_share(m, input_shares[agg_id])
        assert len(blob) == wire.input_share_size(m, agg_id)
        assert wire.decode_input_share(m, agg_id, blob) == \
            input_shares[agg_id]
        report = wire.encode_report(m, agg_id, nonce, public_share,
                                    input_shares[agg_id])
        (rn, rps, rshare) = wire.decode_report(m, agg_id, report)
        assert rn == nonce and rps == public_share \
            and rshare == input_shares[agg_id]

    level = len(alpha) - 1
    agg_param = (level, (alpha,), True)
    verify_key = gen_rand(m.VERIFY_KEY_SIZE)
    states = []
    shares = []
    for agg_id in range(2):
        (state, share) = m.prep_init(verify_key, ctx, agg_id, agg_param,
                                     nonce, public_share,
                                     input_shares[agg_id])
        states.append(state)
        shares.append(share)
        blob = encode_prep_share(m, share)
        assert len(blob) == wire.prep_share_size(m, agg_param)
        assert wire.decode_prep_share(m, agg_param, blob) == share
    prep_msg = m.prep_shares_to_prep(ctx, agg_param, shares)
    assert wire.decode_prep_msg(m, agg_param, prep_msg or b"") == \
        prep_msg
    out = m.prep_next(ctx, states[0], prep_msg)
    agg = m.agg_update(agg_param, m.agg_init(agg_param), out)
    blob = encode_agg_share(m, agg)
    assert len(blob) == wire.agg_share_size(m, agg_param)
    assert wire.decode_agg_share(m, agg_param, blob) == agg


def _load_vector(name: str) -> dict:
    with open(os.path.join(TEST_VEC_DIR, name)) as f:
        return json.load(f)


def _subprocess_round(mastic, spec, vec):
    from mastic_tpu.drivers.parties import ProcessCollector

    ctx = bytes.fromhex(vec["ctx"])
    verify_key = bytes.fromhex(vec["verify_key"])
    reports = []
    for prep in vec["prep"]:
        nonce = bytes.fromhex(prep["nonce"])
        public_share = mastic.vidpf.decode_public_share(
            bytes.fromhex(prep["public_share"]))
        input_shares = [
            wire.decode_input_share(mastic, agg_id,
                                    bytes.fromhex(raw))
            for (agg_id, raw) in enumerate(prep["input_shares"])
        ]
        reports.append((nonce, public_share, input_shares))
    agg_param = mastic.decode_agg_param(bytes.fromhex(vec["agg_param"]))

    coll = ProcessCollector(mastic, spec, ctx, verify_key)
    try:
        coll.upload(reports)
        (result, accept, share_bytes) = coll.round(agg_param)
    finally:
        coll.close()
    return (result, accept, share_bytes)


@pytest.mark.parametrize("name,spec", [
    ("MasticCount_0.json", {"class": "MasticCount", "args": [2]}),
    ("MasticHistogram_0.json",
     {"class": "MasticHistogram", "args": [2, 4, 2]}),
])
def test_process_separated_conformance(name, spec) -> None:
    """Two OS processes reproduce the vector's aggregate shares byte
    for byte (incl. a joint-rand instantiation)."""
    vec = _load_vector(name)
    from mastic_tpu.drivers.parties import instantiate

    mastic = instantiate(spec)
    assert vec["vidpf_bits"] == mastic.vidpf.BITS
    (result, accept, share_bytes) = _subprocess_round(mastic, spec, vec)
    assert accept.all()
    assert [share_bytes[0].hex(), share_bytes[1].hex()] == \
        vec["agg_shares"]
    assert result == vec["agg_result"]


def test_resolve_rejects_malformed_peer_blob() -> None:
    """A truncated or oversized prep-share exchange is refused as a
    protocol error, not a numpy reshape traceback (ADVICE r4)."""
    from mastic_tpu.drivers.parties import AggregatorParty

    m = MasticCount(2)
    ctx = b"wire test"
    verify_key = gen_rand(m.VERIFY_KEY_SIZE)
    blobs = []
    for alpha in ((True, False), (False, True)):
        nonce = gen_rand(m.NONCE_SIZE)
        rand = gen_rand(m.RAND_SIZE)
        (ps, shares) = m.shard(ctx, (alpha, 1), nonce, rand)
        blobs.append([wire.encode_report(m, a, nonce, ps, shares[a])
                      for a in range(2)])
    parties = [AggregatorParty(m, a, verify_key, ctx)
               for a in range(2)]
    for a in range(2):
        parties[a].load_reports([b[a] for b in blobs])
    agg_param = (0, ((False,), (True,)), True)
    _leader_blob = parties[0].prep_blob(agg_param)
    helper_blob = parties[1].prep_blob(agg_param)

    with pytest.raises(ValueError, match="malformed prep-share"):
        parties[0].resolve(agg_param, helper_blob[:-1])
    with pytest.raises(ValueError, match="malformed prep-share"):
        parties[0].resolve(agg_param, helper_blob + b"\x00")
    (accept, resolution) = parties[0].resolve(agg_param, helper_blob)
    assert accept.all()

    # Symmetric guard on the helper side: a truncating leader is a
    # protocol error, whether the bitmap or a prep-msg frame is cut.
    with pytest.raises(ValueError, match="malformed resolution"):
        parties[1].confirm(agg_param, b"")
    with pytest.raises(ValueError, match="truncated"):
        parties[1].confirm(agg_param, resolution[:-1])
    assert parties[1].confirm(agg_param, resolution).all()


def test_process_separated_rejects_tampered_report() -> None:
    """A tampered VIDPF key is rejected by the process-separated
    round (the accept bitmap excludes it) without disturbing honest
    reports."""
    spec = {"class": "MasticCount", "args": [2]}
    vec = _load_vector("MasticCount_0.json")
    from mastic_tpu.drivers.parties import instantiate

    mastic = instantiate(spec)
    ctx = bytes.fromhex(vec["ctx"])
    verify_key = bytes.fromhex(vec["verify_key"])
    reports = []
    for (i, prep) in enumerate(vec["prep"]):
        nonce = bytes.fromhex(prep["nonce"])
        public_share = mastic.vidpf.decode_public_share(
            bytes.fromhex(prep["public_share"]))
        input_shares = [
            wire.decode_input_share(mastic, agg_id,
                                    bytes.fromhex(raw))
            for (agg_id, raw) in enumerate(prep["input_shares"])
        ]
        if i == 0:  # flip a key bit of the leader's share
            (key, proof, seed, part) = input_shares[0]
            key = bytes([key[0] ^ 1]) + key[1:]
            input_shares[0] = (key, proof, seed, part)
        reports.append((nonce, public_share, input_shares))
    agg_param = mastic.decode_agg_param(bytes.fromhex(vec["agg_param"]))

    from mastic_tpu.drivers.parties import ProcessCollector

    coll = ProcessCollector(mastic, spec, ctx, verify_key)
    try:
        coll.upload(reports)
        (result, accept, _shares) = coll.round(agg_param)
    finally:
        coll.close()
    assert not accept[0] and accept[1:].all()

    # The honest remainder must equal the oracle over those reports.
    measurements = [vec["prep"][i]["measurement"]
                    for i in range(1, len(vec["prep"]))]
    (level, prefixes, _wc) = agg_param
    expected = []
    for prefix in prefixes:
        total = 0
        for raw in measurements:
            (alpha_raw, weight) = raw
            alpha = tuple(bool(b) for b in alpha_raw)
            if alpha[:level + 1] == tuple(prefix):
                total += weight
        expected.append(total)
    assert result == expected
