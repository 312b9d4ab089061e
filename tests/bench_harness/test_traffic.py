"""The seed decides token ids, never the order or how much work a run holds."""
import pytest

from benchmark import traffic

VOCAB = 32000
SEEDS = (0, 7, 3_000_000_001)
MIXES = ("batch-closed", "docqa-open")


def _block(spec, seed, b):
    if spec["loop"] == "closed":
        return traffic.closed_block(spec, seed, b, VOCAB)
    return traffic.open_block(spec, seed, b, VOCAB)


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("block", (0, 1, 5))
def test_blocks_have_equal_multisets_across_seeds(mix, block):
    spec = traffic.load(mix)
    shapes = []
    for seed in SEEDS:
        reqs = _block(spec, seed, block)
        shapes.append(sorted((len(r["prompt"]), r["max_tokens"]) for r in reqs))
    assert shapes[0] == shapes[1] == shapes[2]


@pytest.mark.parametrize("mix", MIXES)
def test_every_block_is_the_same_work(mix):
    spec = traffic.load(mix)
    work = {
        (sum(len(r["prompt"]) for r in _block(spec, 1, b)),
         sum(r["max_tokens"] for r in _block(spec, 1, b)))
        for b in range(3)
    }
    assert len(work) == 1


def test_seed_changes_tokens_only_and_repeats():
    spec = traffic.load("batch-closed")
    a = traffic.closed_block(spec, 1, 0, VOCAB)
    b = traffic.closed_block(spec, 2, 0, VOCAB)
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert a[0]["prompt"][:8] != b[0]["prompt"][:8]
    assert a == traffic.closed_block(spec, 1, 0, VOCAB)
    assert "order" not in spec  # no knob: the mix keys every shuffle


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_replays_one_schedule_with_other_tokens(mix):
    spec = traffic.load(mix)
    a, b = _block(spec, 1, 2), _block(spec, 3_000_000_001, 2)
    assert [(len(r["prompt"]), r["max_tokens"], r.get("due")) for r in a] == [
        (len(r["prompt"]), r["max_tokens"], r.get("due")) for r in b]
    assert all(x["prompt"][:16] != y["prompt"][:16] for x, y in zip(a, b))
    # blocks differ in order among themselves
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in _block(spec, 1, 3)]


def test_closed_lengths_respect_the_clip():
    spec = traffic.load("batch-closed")
    reqs = traffic.closed_block(spec, 3, 0, VOCAB)
    assert len(reqs) == spec["clients"] == spec["block"]
    assert all(spec["prompt"]["min"] <= len(r["prompt"]) <= spec["prompt"]["max"] for r in reqs)
    assert all(spec["output"]["min"] <= r["max_tokens"] <= spec["output"]["max"] for r in reqs)
    assert all(traffic.FIRST_TOKEN_ID <= t < VOCAB for r in reqs for t in r["prompt"])


def test_open_gaps_and_sharing_do_not_depend_on_the_seed():
    spec = traffic.load("docqa-open")
    per_seed = []
    for seed in SEEDS:
        reqs = traffic.open_block(spec, seed, 0, VOCAB)
        firsts = sorted(r["due"] for r in reqs if r["id"].endswith(".a0"))
        gaps = sorted(round(b - a, 9) for a, b in zip([0.0] + firsts, firsts))
        docs = sorted(r["doc_tokens"] for r in reqs)
        per_seed.append((gaps, docs, len(reqs)))
    assert per_seed[0] == per_seed[1] == per_seed[2]
    d, asks = spec["block_documents"], spec["asks_per_document"]
    assert per_seed[0][2] == d * asks
    # a block lasts exactly documents * asks / rate seconds
    assert sum(per_seed[0][0]) == pytest.approx(d * asks / spec["rate_per_s"])


def test_asks_of_one_document_share_its_tokens():
    spec = traffic.load("docqa-open")
    reqs = [r for r in traffic.open_block(spec, 5, 0, VOCAB) if ".d3." in r["id"]]
    n = reqs[0]["doc_tokens"]
    assert len({tuple(r["prompt"][:n]) for r in reqs}) == 1
    assert len({tuple(r["prompt"][n:]) for r in reqs}) == len(reqs)
    dues = sorted(r["due"] for r in reqs)
    lo, hi = spec["ask_delay_s"]["min"], spec["ask_delay_s"]["max"]
    assert all(lo <= b - a <= hi for a, b in zip(dues, dues[1:]))


def test_open_schedule_is_sorted_and_bounded():
    spec = traffic.load("docqa-open")
    sched = traffic.open_schedule(spec, 11, VOCAB, 30.0)
    dues = [r["due"] for r in sched]
    assert dues == sorted(dues) and dues[-1] < 30.0
    assert len(sched) == pytest.approx(30.0 * spec["rate_per_s"], rel=0.35)


@pytest.mark.parametrize("p,want", ((0.5, 0.4549), (0.9, 2.7055)))
def test_gamma_quantile_against_chi_square(p, want):
    # gamma(shape 1/2, scale 2) is chi-square with one degree of freedom
    assert 2 * traffic._gamma_quantile(p, 0.5) == pytest.approx(want, rel=1e-3)
