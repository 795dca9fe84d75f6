"""Model families (``bench/families/<family>.py``) in the harness.

The dense GQA family gives, bit for bit, the tree, reference logits and
counts the harness gave before families existed (pinned below at one
seed); an unknown family fails as the cell is loaded; and a family file
dropped into the families directory runs through the whole harness with
no other benchmark file touched."""
import hashlib
import json
import pathlib
import shutil
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import counts as C
from bench import generate as G
from bench import harness as H
from bench import layer as Lyr
from bench import trace as T
from bench_tiny import load, tiny_chat, tiny_config

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2**31 + 515

# Read at this seed from the harness before the dense code moved into
# bench/families/dense_gqa.py: sha256 over every program leaf's path,
# dtype, shape and bytes.
DENSE_PARAMS_SHA256 = \
    "c5a3ad8bf5e8a921aadef71ed36be784a095789c6438b41d3ad5f69c99da5def"
# The reference's logits of TOKENS at [:, ::5, ::97], and their sum.
DENSE_LOGITS = np.array([
    [[0.06734849, -0.0024780985, 0.1081726, -0.2706192, 0.07111119,
      -0.03555157],
     [-0.37936237, 0.027546834, -0.2832021, 0.19677132, -0.06735999,
      0.10135409],
     [-0.05082398, -0.23430939, 0.12503809, 0.2316662, 0.23480318,
      0.08789653],
     [-0.17651004, 0.01847386, 0.1492744, 0.5252017, 0.0037550826,
      0.06251022],
     [-0.2591345, -0.0143218, 0.1678557, 0.084160194, 0.030166548,
      -0.024142157]],
    [[0.18453406, 0.36946562, -0.41946098, -0.18819483, -0.38764122,
      -0.030511338],
     [-0.23859087, -0.12313997, -0.13042799, -0.36408955, -0.01807071,
      -0.037107345],
     [0.2725768, 0.13067214, -0.09334197, -0.39216673, 0.08513874,
      -0.297092],
     [-0.0674064, 0.13942169, -0.017414104, -0.2757868, -0.18534908,
      -0.19890027],
     [0.12224224, -0.019840248, -0.1348443, -0.20121278, 0.063136674,
      -0.009821442]]], np.float32)
DENSE_LOGITS_SUM = -9.481496401553159
# counts.decode_step at contexts [0, 5, 17, 100] and []; prefill 0..32
# without the head and 16..48 with it; KV bytes per token.
DENSE_DECODE = (916480.0, 246016.0)
DENSE_DECODE_IDLE = (0.0, 213760.0)
DENSE_PREFILL = (4988928.0, 5316608.0)
DENSE_KV = 256


def tokens(vocab):
    return (np.arange(48, dtype=np.int32).reshape(2, 24) * 37 + 11) % vocab


def tree_sha256(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.view(np.uint16).tobytes() if a.dtype.itemsize == 2
                 else a.tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# the dense family keeps the parent's numbers
# ---------------------------------------------------------------------------

def test_a_file_without_family_is_dense_gqa():
    cfg = load("configs", "qwen3-1.7b")
    assert "family" not in cfg
    assert pathlib.Path(H.family(cfg).__file__) == \
        H.FAMILY_DIR / "dense_gqa.py"


def test_dense_family_program_tree_is_pinned():
    eng = H.build_engine(tiny_config(), SEED)
    assert tree_sha256(eng.params) == DENSE_PARAMS_SHA256


def test_dense_family_reference_logits_are_pinned():
    cfg = tiny_config()
    got = H.family(cfg).Reference(cfg, SEED).logits(
        tokens(cfg["vocab_size"]))
    assert got.shape == (2, 24, 512)
    # float32 sums in another order move the last bits only
    np.testing.assert_allclose(got[:, ::5, ::97], DENSE_LOGITS, rtol=0,
                               atol=2e-6)
    assert float(got.astype(np.float64).sum()) == pytest.approx(
        DENSE_LOGITS_SUM, rel=1e-6)


def test_dense_family_counts_are_pinned():
    cfg = tiny_config()
    fam, run = H.family(cfg), SimpleNamespace(config=cfg)
    for got in (fam.decode_step(run, [0, 5, 17, 100]),
                C.decode_step(cfg, [0, 5, 17, 100])):
        assert got == DENSE_DECODE
    assert fam.decode_step(run, []) == DENSE_DECODE_IDLE
    assert (fam.prefill(run, 0, 32, head=False),
            fam.prefill(run, 16, 48, head=True)) == DENSE_PREFILL
    assert fam.kv_bytes_per_token(cfg) == DENSE_KV


# ---------------------------------------------------------------------------
# an unknown family
# ---------------------------------------------------------------------------

def bench_root(tmp_path, config: dict) -> pathlib.Path:
    """A checkout root whose one configuration is ``config``, served by
    the chat cell's traffic and limits."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"][0]["file"] = "config.json"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "config.json").write_text(json.dumps(config))
    return tmp_path


def test_unknown_family_fails_in_load_cell(tmp_path):
    cfg = dict(load("configs", "qwen3-1.7b"), family="no_such_family")
    root = bench_root(tmp_path, cfg)
    with pytest.raises(ValueError, match="no model family 'no_such_family'"
                       r".*families present: \['dense_gqa'\]"):
        H.load_cell("qwen3-1.7b.chat", root=root)


# ---------------------------------------------------------------------------
# a family file drops in
# ---------------------------------------------------------------------------

# At this size a sound run's widest gap reads about 1e-3 and the float8
# control's above 3e-2 (CPU); the limit sits between them, as in
# test_bench_harness.py.
TINY_LIMIT = 0.01
MS = 1_000_000


def moe_config() -> dict:
    """Every token takes all four experts.  At two of four the program's
    router, fed bfloat16 activations, and the float32 reference's part
    ways where the second and third expert nearly tie: on the CPU, 3 of 4
    seeds read one token each at a gap of 0.26-1.19, each where the
    reference's two lie within 0.0011 of probability."""
    cfg = tiny_config()
    cfg.update(name="tiny-moe", family="tiny_moe",
               model="qwen3-moe-235b-a22b", architectures=[
                   "Qwen3MoeForCausalLM"], tie_word_embeddings=False,
               num_experts=4, num_experts_per_tok=4,
               moe_intermediate_size=32, norm_topk_prob=True)
    return cfg


def decode_trace(launches: int):
    """A device trace of ``launches`` 2-ms decode launches in a window."""
    mods = [("jit_decode_multi_paged(1)", (3 * i + 1) * MS, 2 * MS)
            for i in range(launches)]
    ops = [("fusion.1", s, d) for _, s, d in mods]
    return T.from_planes([
        ("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", mods)]),
        ("/host:CPU", [("python3", [("bench.window", 0,
                                     3 * launches * MS)])])])


@pytest.fixture(scope="module")
def moe(tmp_path_factory):
    """The tiny expert family served through the harness from a families
    directory that holds only its file."""
    fams = tmp_path_factory.mktemp("families")
    shutil.copy(pathlib.Path(__file__).with_name("tiny_moe_family.py"),
                fams / "tiny_moe.py")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(H, "FAMILY_DIR", fams)
        cell = H.load_cell("qwen3-1.7b.chat", root=bench_root(
            tmp_path_factory.mktemp("root"), moe_config()))
        mix = tiny_chat()
        eng = H.build_engine(cell.config, SEED)
        arch = eng.model.cfg
        tree = jax.tree.map(lambda a: a.shape, eng.params)
        H.warm_up(eng, cell.config, mix, SEED)
        recorder = H.Recorder(eng, cell.config)
        sched = G.generate(mix, vocab=H.vocab(cell.config), seed=SEED,
                           seconds=3.0, rate_per_s=8.0)
        run = H.drive(eng, sched, 3.0,
                      trace_dir=tmp_path_factory.mktemp("trace"),
                      recorder=recorder)
        seqs = H.sample_for_check(run, 8, SEED)
        recorder.eng = None
        del eng
        H.free()
    return SimpleNamespace(fams=fams, cfg=cell.config, mix=mix, arch=arch,
                           tree=tree, seqs=seqs, recorder=recorder)


def test_dropped_in_family_builds_its_own_program(moe):
    assert moe.arch.family == "moe" and moe.arch.n_experts == 4 \
        and moe.arch.top_k == 4 and moe.arch.expert_d_ff == 32
    layers = moe.tree["layers"]
    assert "mlp" not in layers
    assert layers["moe"]["w_gate"] == (2, 4, 64, 32)
    assert moe.tree["unembed"] == (64, 512)


def test_dropped_in_family_sound_run_is_correct(moe, monkeypatch):
    monkeypatch.setattr(H, "FAMILY_DIR", moe.fams)
    got = H.check(moe.cfg, moe.mix, moe.seqs, SEED, limit=TINY_LIMIT)
    assert got["ok"] and got["tokens"] > 50, got


def test_dropped_in_family_control_fails(moe, monkeypatch):
    monkeypatch.setattr(H, "FAMILY_DIR", moe.fams)
    got = H.check(moe.cfg, moe.mix, moe.seqs, SEED, limit=TINY_LIMIT,
                  control=True)
    assert not got["ok"] and got["max_logit_gap"] > 3 * TINY_LIMIT, got


def test_dropped_in_family_counts_feed_the_readers(moe, monkeypatch):
    monkeypatch.setattr(H, "FAMILY_DIR", moe.fams)
    rec = moe.recorder
    assert rec.decodes and rec.prefills and rec.step_s
    tr = decode_trace(len(rec.decodes))
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    run = SimpleNamespace(config=moe.cfg, chips=1, trace=tr,
                          window=tr.window(), recorder=rec, peak=peak,
                          notes={})
    roof, mfu = Lyr.decode_roofline(run), Lyr.mfu(run)
    assert 0 < roof < 100 and 0 < mfu < 100
    fam = H.family(moe.cfg)

    def least(counts_of):
        return [max(fl / peak["bf16_flops"], by / peak["hbm_bytes_per_s"])
                for fl, by in map(counts_of, rec.decodes)]

    mine = least(lambda c: fam.decode_step(run, c))
    assert mine != least(lambda c: C.decode_step(moe.cfg, c))
    # every launch takes 2 ms in the trace
    assert roof == pytest.approx(100 * np.mean(mine) * 1e3 / 2.0)
    flops = sum(fam.decode_step(run, c)[0] for c in rec.decodes) \
        + sum(fam.prefill(run, s, e, head=True) for s, e in rec.prefills)
    assert mfu == pytest.approx(100 * flops / (sum(rec.step_s)
                                               * peak["bf16_flops"]))
