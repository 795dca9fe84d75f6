"""The engine's profiler spans and admission counters: under a real
``jax.profiler`` trace every serving mode emits the same span tree, whose
arguments agree with what each launch saw and with ``EngineStats``; with
no profiler session no span argument is ever built."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model, local_plan
from repro.serving import Engine, EngineKnobs, Request

PHASES = ["prepare", "launch", "sync", "commit"]
STEP_CHILDREN = {"engine.housekeeping", "engine.admit", "engine.prefill",
                 "engine.decode"}
# mode -> (engine options, the jitted decode entry point it launches)
MODES = {
    "paged_h1": (dict(horizon=1), "_decode_multi_jit"),
    "paged_h4": (dict(horizon=4), "_decode_multi_jit"),
    "chunked_shared": (dict(prefill_chunk=16, prefix_share=True),
                       "_decode_multi_jit"),
    "ngram": (dict(draft="ngram", spec_k=3, horizon=2), "_decode_spec_jit"),
    "slots": (dict(paged=False), "_decode_jit"),
}
ARG_BUILDERS = ("_step_args", "_admit_args", "_prefill_args", "_decode_args")


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama2-7b").smoke_config()
    return build_model(cfg, local_plan(param_dtype=jnp.bfloat16))


@pytest.fixture(scope="module")
def tiny_params(tiny_model):
    return tiny_model.init(jax.random.PRNGKey(0))


def _engine(model, params, **kw):
    kw.setdefault("paged", True)
    return Engine(model, params, max_seq=64, n_slots=3, block_size=8,
                  knobs=EngineKnobs(max_batch=3), **kw)


def _submit(eng, vocab, n_req=5):
    """Five requests for three lanes; all share a 24-token head, so the
    prefix index has whole blocks to hand out."""
    rng = np.random.default_rng(0)
    head = [int(t) for t in rng.integers(0, vocab, 24)]
    for i in range(n_req):
        tail = [int(t) for t in rng.integers(0, vocab, 4 + 3 * i)]
        eng.submit(Request(prompt=head + tail, max_new_tokens=5 + i))


def _read_spans(trace_dir):
    """``(start, end, name, args)`` of every ``engine.*`` host event."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats)))
    out.sort(key=lambda s: (s[0], -s[1]))
    return out


def _tree(spans):
    """Nest the spans by containment: ``[(span, children), ...]``."""
    roots, stack = [], []
    for sp in spans:
        node = (sp, [])
        while stack and stack[-1][0][1] < sp[1]:
            stack.pop()
        (stack[-1][1] if stack else roots).append(node)
        stack.append(node)
    return roots


@pytest.mark.parametrize("mode", sorted(MODES))
def test_spans_under_a_profiler(tiny_model, tiny_params, tmp_path, mode):
    opts, entry = MODES[mode]
    eng = _engine(tiny_model, tiny_params, **opts)
    launches = []
    jitted = getattr(eng, entry)

    def spy(*a, **kw):
        lane_of = eng.pool.lane_of if eng.paged else eng.pool.slot_of
        launches.append((len(eng.active),
                         sum(int(eng.pool.lengths[lane_of[r]])
                             for r in eng.active)))
        return jitted(*a, **kw)

    setattr(eng, entry, spy)
    _submit(eng, tiny_model.cfg.vocab_size)
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=po)
    try:
        steps = 0
        while eng.queue or eng.active or eng.prefilling:
            eng.step()
            steps += 1
    finally:
        jax.profiler.stop_trace()
    assert len(eng.stats.completed) == 5

    roots = _tree(_read_spans(tmp_path))
    # one engine.step per step() call, numbered in order, holding the rest
    assert [r[0][2] for r in roots] == ["engine.step"] * steps
    assert [r[0][3]["step_num"] for r in roots] == list(range(1, steps + 1))
    assert roots[0][0][3]["queue"] == 5
    admits, prefills, decodes = [], [], []
    for (_, _, _, args), children in roots:
        assert {"queue", "active", "prefilling"} <= set(args)
        names = [c[0][2] for c in children]
        assert set(names) <= STEP_CHILDREN
        assert names.count("engine.admit") == 1
        for node in children:
            if node[0][2] == "engine.admit":
                admits.append(node[0][3])
                # batched (paged) and per-request (slots) prefill run
                # inside admission; chunked prefill runs in its own tick
                prefills += [c for c in node[1]
                             if c[0][2] == "engine.prefill"]
            elif node[0][2] == "engine.prefill":
                prefills.append(node)
            elif node[0][2] == "engine.decode":
                decodes.append(node)
    assert prefills and decodes
    for (_, _, name, args), children in prefills + decodes:
        got = [c[0][2] for c in children]
        want = [f"{name}.{p}" for p in PHASES]
        if name == "engine.prefill" and not args["finished"]:
            want.remove("engine.prefill.sync")    # a chunk reads nothing
        assert got == want
    # each decode span saw what its launch saw
    assert [(d[0][3]["lanes"], d[0][3]["ctx_tokens"])
            for d in decodes] == launches
    # each launch names the attention its program was built with: off TPU
    # the XLA gather, as the counter says
    assert [c[0][3]["attn"] for d in decodes for c in d[1]
            if c[0][2] == "engine.decode.launch"] == ["xla"] * len(launches)
    assert eng.stats.decode_launches == {"pallas": 0, "xla": len(launches)}
    horizon = eng.horizon if eng.paged else 1
    assert all(d[0][3]["horizon"] == horizon and d[0][3]["preempted"] == 0
               for d in decodes)
    assert sum(p[0][3]["tokens"] for p in prefills) \
        == eng.stats.prefill_tokens
    assert sum(p[0][3]["finished"] for p in prefills) == 5
    # the admission counters agree with the spans' arguments
    assert sum(a["requests"] for a in admits) == eng.stats.admitted == 5
    assert sum(a["queue_wait_ms"] for a in admits) == pytest.approx(
        eng.stats.queue_wait_s * 1e3, rel=1e-6)
    assert eng.stats.queue_wait_s > 0
    assert sum(a["prompt_tokens"] for a in admits) == sum(
        len(r.prompt) for r in eng.stats.completed)
    shared = sum(a["shared_tokens"] for a in admits)
    assert (shared > 0) == (mode == "chunked_shared")


@pytest.mark.parametrize("mode", ["chunked_shared", "paged_h1", "slots"])
def test_no_span_arguments_without_a_profiler(tiny_model, tiny_params,
                                              mode):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    eng = _engine(tiny_model, tiny_params, **MODES[mode][0])
    calls = []
    for name in ARG_BUILDERS:
        def never(*a, _name=name, **kw):
            calls.append(_name)
            return {}
        setattr(eng, name, never)
    _submit(eng, tiny_model.cfg.vocab_size)
    eng.run()
    assert len(eng.stats.completed) == 5
    assert calls == []
    assert eng.stats.admitted == 5 and eng.stats.queue_wait_s > 0
    assert eng.stats.decode_launches["pallas"] == 0
    assert eng.stats.decode_launches["xla"] > 0


def test_queue_wait_restarts_on_preemption(tiny_model, tiny_params):
    """A preempted request re-enters the queue with a fresh stamp and is
    counted again when it claims a lane anew."""
    eng = _engine(tiny_model, tiny_params)
    _submit(eng, tiny_model.cfg.vocab_size, n_req=2)
    eng.step()
    assert eng.stats.admitted == 2
    rid = max(eng.active)
    before = eng.active[rid].queued_ns
    eng._preempt([rid])
    assert eng.queue[0].req_id == rid and eng.queue[0].queued_ns > before
    eng.step()
    assert eng.stats.admitted == 3


def test_decode_attention_dispatch(monkeypatch):
    """The Pallas decode kernel is chosen from what the code observes: a
    TPU backend, a single-device plan, query head h on kv head h // G, a
    lane-multiple head dim and a pool of the compute dtype whose kv heads
    fill packed rows.  Anything else keeps the XLA gather."""
    from types import SimpleNamespace

    from repro.models.attention import paged_decode_path
    from repro.models.sharding import ShardPlan
    cfg = get_config("qwen3-1.7b")
    one = local_plan(param_dtype=jnp.bfloat16)
    assert paged_decode_path(cfg, one, jnp.bfloat16) == "xla"       # CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged_decode_path(cfg, one, jnp.bfloat16) == "pallas"
    # 16 query heads over a 3-way model axis pad to 18
    padded = ShardPlan(mesh=SimpleNamespace(shape={"model": 3}, size=3),
                       tp_axis="model", shard_paged_pool=False)
    assert padded.h_pad(cfg) != cfg.n_heads
    assert paged_decode_path(cfg, padded, jnp.bfloat16) == "xla"
    # head dim 64; one bf16 kv head; a float32 pool under bf16 compute
    assert paged_decode_path(get_config("granite-moe-3b-a800m"), one,
                             jnp.bfloat16) == "xla"
    assert paged_decode_path(cfg.replace(n_heads=8, n_kv_heads=1), one,
                             jnp.bfloat16) == "xla"
    assert paged_decode_path(cfg, one, jnp.float32) == "xla"
