import numpy as np

import pool_model
from harness import manifest, traffic


def play(spec, seed):
    reqs = traffic.generate(spec, seed, spec["preroll_s"] + 51.0, 50272)
    return pool_model.play(reqs, spec["preroll_s"], 51.0, 6, 64,
                           5.2e-3, 0.63e-6, 4.3e-3)


def test_below_the_knee_the_model_keeps_up_and_above_it_the_queue_grows():
    gaps, occ, backlog = play(manifest.traffic_of("chat_poisson"), 11)
    assert 3.0 < occ < 4.6 and backlog < 12
    # plain steps and steps that carry a chunk: p95 lies among the latter
    assert 5.2e-3 < np.percentile(gaps, 50) < 8e-3
    assert 9.5e-3 < np.percentile(gaps, 95) < 13e-3
    gaps, occ, backlog = play(manifest.traffic_of("docs_saturated"), 11)
    assert backlog > 60 and np.percentile(gaps, 5) > 9.5e-3   # a chunk a pass


def test_a_pass_costs_what_is_live():
    one = [{"due": 0.0, "prompt": np.ones(64, np.int32), "new_tokens": 5}]
    gaps, occ, backlog = pool_model.play(one, 0.0, 1.0, 6, 64, 5e-3, 1e-6, 4e-3)
    # one chunk, its token, then four steps over 65, 66, 67 and 68 places
    assert np.allclose(gaps, [5e-3 + 1e-6 * (65 + i) for i in range(4)])
    assert occ == 0.8 and backlog == 0       # no slot decodes in the chunk pass
