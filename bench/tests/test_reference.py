"""The float32 reference against the paged engine on the CPU, at a small
size: chunked prefill (several chunks, every bucket) and decode through
the paged cache must give the reference's logits at every position."""
import numpy as np
import pytest

import helpers  # noqa: F401  (puts the repo and src on the path)
from bench.generators import closed
from bench.models import dense
from bench.reference import dense as reference

# bf16 weights and activations against float32 at full precision: logits
# of size ~1 differ by a few bf16 steps (2^-8 relative) per layer; a wrong
# position, mask or bias moves them by their own size.
REL_TOL = 0.05


@pytest.fixture(scope="module")
def engine_logits(tmp_path_factory):
    cfg = helpers.TINY_CONFIG
    mix = dict(helpers.TINY_MIX, clients=2)
    weights = dense.init_weights(cfg, seed=12345)
    plan = tmp_path_factory.mktemp("plans") / "plans.json"
    served = dense.Served(cfg, mix, dense.program(cfg, weights),
                          device_kind=None,
                          plan_path=plan, generators=closed)
    rows = {}           # (request id, position) -> engine logits
    with served:
        eng = served.engine
        art = eng.art
        prefill, decode = art.prefill_fn, art.decode_fn
        buckets = set()

        def prefill_fn(params, state, chunk, slot, start, true_len, blocks):
            logits, state = prefill(params, state, chunk, slot, start,
                                    true_len, blocks)
            st = eng.sched.slots[int(slot)]
            buckets.add(chunk.shape[1])
            pos = int(start) + int(true_len) - 1
            rows[(st.request.request_id, pos)] = np.asarray(logits)
            return logits, state

        def decode_fn(params, state, tokens, active):
            lengths = np.asarray(state["kv"].length)
            logits, state = decode(params, state, tokens, active)
            out = np.asarray(logits)
            for lane in np.flatnonzero(np.asarray(active)):
                st = eng.sched.slots[lane]
                rows[(st.request.request_id, int(lengths[lane]))] = out[lane]
            return logits, state

        art.prefill_fn, art.decode_fn = prefill_fn, decode_fn
        rng = np.random.default_rng(7)
        reqs = [served.request_cls(
            prompt=rng.integers(0, cfg["vocab_size"], n, dtype=np.int32),
            max_new_tokens=5) for n in (36, 9, 7)]
        for r in reqs:
            eng.submit(r)
        while not eng.sched.idle:
            eng.tick()
        done = {st.request.request_id: st for st in eng.finished}
    return weights, reqs, done, rows, buckets


def test_chunked_prefill_and_decode_match_reference(engine_logits):
    weights, reqs, done, rows, buckets = engine_logits
    # 36 = 16 + 16 + 4, 9 and 7 tokens: every bucket runs
    assert buckets == {4, 8, 16}, buckets
    m = dense.dims(helpers.TINY_CONFIG)
    for r in reqs:
        st = done[r.request_id]
        seq = np.concatenate([r.prompt, np.asarray(st.tokens[:-1], np.int32)])
        positions = np.arange(r.prompt_len - 1, len(seq))
        ref = reference.logits(weights, m, seq, positions)
        got = np.stack([rows[(r.request_id, int(p))][: m["vocab"]]
                        for p in positions])
        err = np.abs(got - ref).max() / np.abs(ref).max()
        assert err < REL_TOL, (r.prompt_len, err)
        assert (got.argmax(-1) == ref.argmax(-1)).mean() > 0.5


def test_reference_is_causal_and_padding_free():
    m = dense.dims(helpers.TINY_CONFIG)
    weights = dense.init_weights(helpers.TINY_CONFIG, seed=3)
    seq = np.arange(1, 40, dtype=np.int32)
    short = reference.logits(weights, m, seq[:20], np.arange(20))
    full = reference.logits(weights, m, seq, np.arange(20))
    np.testing.assert_allclose(short, full, rtol=1e-5, atol=1e-5)
