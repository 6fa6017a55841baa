"""EC placement solver: failure-domain budget (deploy/data_placement analog)."""

import pytest

from t3fs.mgmtd.placement import select_ec_chains, validate_ec_chains
from t3fs.mgmtd.types import ChainInfo, ChainTargetInfo, PublicTargetState, RoutingInfo


def make_routing(chain_node_pairs):
    r = RoutingInfo()
    for cid, node in chain_node_pairs:
        r.chains[cid] = ChainInfo(cid, 1, [
            ChainTargetInfo(cid * 100, node, PublicTargetState.SERVING)])
    return r


def test_select_respects_node_budget():
    # 10 chains over 5 nodes (2 each): EC(8+2) fits with max 2 per node
    routing = make_routing([(c, (c - 1) % 5 + 1) for c in range(1, 11)])
    chains = select_ec_chains(routing, 8, 2)
    assert len(chains) == 10
    assert validate_ec_chains(routing, chains, 2)


def test_select_fails_on_narrow_topology():
    # 10 chains over 3 nodes: some node must host >= 4 shards > m=2
    routing = make_routing([(c, (c - 1) % 3 + 1) for c in range(1, 11)])
    with pytest.raises(ValueError):
        select_ec_chains(routing, 8, 2)
    assert not validate_ec_chains(routing, list(range(1, 11)), 2)


def test_select_skips_overloaded_chains():
    # 4 nodes; node 1 has many chains — solver must spread, not take first k+m
    pairs = [(1, 1), (2, 1), (3, 1), (4, 2), (5, 2), (6, 3), (7, 3), (8, 4)]
    routing = make_routing(pairs)
    chains = select_ec_chains(routing, 4, 2, candidates=list(range(1, 9)))
    assert validate_ec_chains(routing, chains, 2)
    assert 3 not in chains  # third chain on node 1 must be skipped


# ---- recovery-traffic balancing (BIBD objective) ----

def test_build_chain_table_10x50_balanced():
    """10-node/50-chain topology: reconstruction load within the integer
    optimum's band (pair counts in {floor(λ), ceil(λ)})."""
    import itertools
    from collections import Counter

    from t3fs.mgmtd.placement import (
        build_chain_table, pair_counts, recovery_imbalance, recovery_load,
    )

    a = build_chain_table(10, 50, 3)
    assert len(a) == 50 and all(len(set(ch)) == 3 for ch in a)
    assert all(1 <= n <= 10 for ch in a for n in ch)
    # per-node chain counts perfectly balanced (150/10)
    per_node = Counter(n for ch in a for n in ch)
    assert sorted(per_node.values()) == [15] * 10
    # pairwise co-occurrence within the integer-optimal band around
    # λ = r(r-1)C/(N(N-1)) = 3.33: every pair in {3, 4}
    pc = pair_counts(a, 10)
    vals = [pc.get(p, 0) for p in itertools.combinations(range(1, 11), 2)]
    assert min(vals) >= 3 and max(vals) <= 4, (min(vals), max(vals))
    # any single failure: peers share recovery within 10% of each other's
    # mean bar integer rounding (max/mean = 4/3.33 = 1.2 is the optimum)
    assert recovery_imbalance(a, 10) <= 1.2 + 1e-9
    for f in (1, 5, 10):
        load = recovery_load(a, 10, f)
        assert sum(load.values()) == 15 * 2   # 15 chains x 2 peers each


def test_build_chain_table_beats_round_robin():
    from t3fs.mgmtd.placement import build_chain_table, pair_counts, _ss

    rr = [[(c + r) % 12 + 1 for r in range(3)] for c in range(48)]
    opt = build_chain_table(12, 48, 3)
    assert _ss(pair_counts(opt, 12)) < _ss(pair_counts(rr, 12))


def test_validate_ec_chains_property():
    """Property check over generated placements: select_ec_chains output
    always satisfies validate_ec_chains (the <= m shards/node invariant)."""
    import random

    from t3fs.mgmtd.placement import select_ec_chains, validate_ec_chains
    from t3fs.mgmtd.types import (
        ChainInfo, ChainTargetInfo, PublicTargetState, RoutingInfo,
    )

    rng = random.Random(4)
    for trial in range(25):
        num_nodes = rng.randint(6, 14)
        num_chains = rng.randint(10, 40)
        routing = RoutingInfo()
        for c in range(1, num_chains + 1):
            width = rng.randint(1, 3)
            members = rng.sample(range(1, num_nodes + 1), width)
            routing.chains[c] = ChainInfo(c, 1, [
                ChainTargetInfo(c * 100 + n, n, PublicTargetState.SERVING)
                for n in members])
        k, m = rng.choice([(4, 2), (8, 2), (6, 3)])
        try:
            picked = select_ec_chains(routing, k, m)
        except ValueError:
            continue  # greedy may legitimately fail on tight topologies
        assert len(picked) == k + m
        assert validate_ec_chains(routing, picked, m), (trial, picked)
