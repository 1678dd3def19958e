"""FLOP and byte counts against hand counts at the cells' shapes."""
import json

from chipbench_testing import BENCH

import counts

IPPO = json.loads((BENCH / "configs" / "ippo_smax.json").read_text())
REC = json.loads((BENCH / "configs" / "rec_ippo_smax.json").read_text())


def test_ippo_forward_and_train_flops():
    # obs 18, actions 8: actor 18-128-128-8, critic 18-128-128-1
    actor = 2 * (18 * 128 + 128 * 128 + 128 * 8)
    critic = 2 * (18 * 128 + 128 * 128 + 128 * 1)
    assert counts.forward_flops_per_agent(IPPO) == (actor, critic) == (39424, 37632)
    # act once, 4 epochs of forward + backward (3x), bootstrap critic once a rollout
    want = 3 * (actor + critic) * 13 + 3 * critic / 128
    assert counts.train_flops_per_env_step(IPPO) == want == 3006066.0


def test_rec_ippo_forward_and_train_flops():
    # encoder 18-128, core projection 128-256, head 128-8 / 128-1
    actor = 2 * (18 * 128 + 128 * 256 + 128 * 8)
    critic = 2 * (18 * 128 + 128 * 256 + 128 * 1)
    assert counts.forward_flops_per_agent(REC) == (actor, critic) == (72192, 70400)
    # plus the critic's re-run over the window for the bootstrap value
    want = 3 * (actor + critic) * 13 + 3 * critic / 128 + 3 * critic
    assert counts.train_flops_per_env_step(REC) == want == 5773938.0


def test_recurrent_scan_bytes_per_update():
    T, H = 128, 128

    def call(B):  # a, b read, h written (T B H); reset (T B); h0 (B H); 4 bytes each
        return 4 * (3 * T * B * H + T * B + B * H)

    # 4 epochs x 4 minibatches of 32 envs x 3 agents x (actor, critic) x (fwd, bwd)
    # + the bootstrap critic over all 128 envs for each agent
    want = 4 * 4 * 3 * 2 * 2 * call(32) + 3 * call(128)
    assert counts.scan_bytes_per_update(REC, 128) == want == 1290141696
