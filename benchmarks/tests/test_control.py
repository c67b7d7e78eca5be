"""The comparison that decides ``correct`` fails when the configuration
is computed in the nearest precision below the one it states. The
control is the reference itself, put in the program's place and held in
bfloat16. Sizes a test run can hold; PERF.md has the readings at the
cells' own sizes. No JAX."""

import manifest as mf

import pytest


def _parts(config: str, **sizes):
    man = mf.load_manifest()
    entry = {c["name"]: c for c in man["configs"]}[config]
    import os
    path = os.path.join(mf.ROOT, entry["file"])
    cfg = mf.with_tiny(mf.load_json(path), True)
    cfg.update(sizes)
    mod = mf.load_module(os.path.splitext(path)[0] + ".py", config)
    return cfg, mod


@pytest.mark.parametrize("seed", [11, 2**31 + 5, 77])
def test_pagerank_in_bfloat16_is_not_correct(seed):
    cfg, mod = _parts("pagerank-1m", nodes=2000, edges=20000, pad_rows=400)
    stream = mod.Stream(cfg, seed, lanes=2)
    stream.load()
    ref = mod.Reference(stream)
    for i in range(6):
        ref.apply(stream.next(i % 2).ref)
    want = ref.expected()
    sound = mod.compare(cfg, want.astype("float32").astype("float64"),
                        want)
    assert all(c.ok for c in sound), sound       # float32 passes
    control = mod.compare(cfg, ref.expected("bfloat16"), want)
    assert not all(c.ok for c in control), control
    assert control[0].value > 2 * control[0].limit


@pytest.mark.parametrize("seed", [11, 2**31 + 5, 77])
def test_tfidf_counts_in_bfloat16_are_not_correct(seed):
    cfg, mod = _parts("tfidf-wiki", docs=1024, tokens_mean=600,
                      tokens_max=4096, vocab=20000, terms=32768,
                      pair_capacity=1 << 20)
    stream = mod.Stream(cfg, seed, lanes=2)
    stream.load()
    ref = mod.Reference(stream)
    for i in range(50):
        ref.apply(stream.next(i % 2).ref)
    want = ref.expected()
    assert all(c.ok for c in mod.compare(cfg, ref.expected(), want))
    control = mod.compare(cfg, ref.expected("bfloat16"), want)
    bad = {c.name: c.value for c in control if not c.ok}
    assert "df_mismatches" in bad, control       # df passes 256


def test_a_dropped_edit_is_not_correct():
    """The exact comparison also catches the delivery guarantee broken:
    one acknowledged edit folded twice, or not at all."""
    cfg, mod = _parts("tfidf-wiki")
    stream = mod.Stream(cfg, 5, lanes=1)
    stream.load()
    full, short = mod.Reference(stream), mod.Reference(stream)
    minted = [stream.next(0) for _ in range(20)]
    for m in minted:
        full.apply(m.ref)
    for m in minted[:-1]:
        short.apply(m.ref)
    assert not all(c.ok for c in mod.compare(cfg, short.expected(),
                                             full.expected()))
