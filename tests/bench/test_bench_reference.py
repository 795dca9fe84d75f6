"""The float32 reference (bench/reference.py) and the benchmark's weights
(bench/weights.py) against the program at a tiny size."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import harness as H
from bench import weights as W
from bench.reference import Reference
from bench_tiny import tiny_config

SEED = 2**31 + 99


def test_one_layer_made_alone_equals_the_stacked_layers():
    tiny = tiny_config()
    dm = W.dims(tiny)
    key = W.seed_key(W.seed_words(SEED))
    stacked = W.all_layers(key, dm)
    for l in range(dm["layers"]):
        alone = W.layer(key, l, dm)
        for name, w in alone.items():
            assert np.array_equal(np.asarray(stacked[name][l]),
                                  np.asarray(w)), name


def test_seeds_past_32_bits_differ():
    a, b = W.seed_words(5), W.seed_words(5 + 2**32)
    assert a.tolist() == [5, 0] and b.tolist() == [5, 1]


def test_reference_matches_the_programs_logits():
    tiny = tiny_config()
    eng = H.build_engine(tiny, SEED)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, tiny["vocab_size"], (2, 40)).astype(np.int32)
    prog = np.asarray(jax.jit(eng.model.logits)(eng.params,
                                                jnp.asarray(tokens)),
                      np.float32)[..., :tiny["vocab_size"]]
    ref = Reference(tiny, SEED).logits(tokens)
    # the program keeps activations and logits in bfloat16 (8 significant
    # bits, a relative step of 2**-8 = 0.4%); a handful of roundings per
    # layer, over 40k logits, stay within 8% of the logits' spread
    scale = ref.std()
    assert np.max(np.abs(prog - ref)) < 0.08 * scale
    assert np.mean(np.abs(prog - ref)) < 0.01 * scale
    # where the reference's top choice leads by more than that, the
    # program picks it too
    top2 = np.sort(ref, axis=-1)[..., -2:]
    sure = (top2[..., 1] - top2[..., 0]) > 0.1 * scale
    assert sure.sum() > 20
    assert np.array_equal(prog.argmax(-1)[sure], ref.argmax(-1)[sure])


def test_other_seed_other_weights():
    tiny = tiny_config()
    tokens = np.arange(16, dtype=np.int32)[None]
    a = Reference(tiny, 1).logits(tokens)
    b = Reference(tiny, 2).logits(tokens)
    assert not np.allclose(a, b)
