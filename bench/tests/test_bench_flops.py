"""The benchmark's FLOP functions against hand counts at a smoke size."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import flops  # noqa: E402

# n=8 agents, F=16, C=4, b=4, K=2, L=3, t=6 test rows:
# d = 16*4 + 4 = 68, din = 68 + 4*(16 + 4) = 148
SMOKE = {"n_agents": 8, "feature_dim": 16, "n_classes": 4,
         "batch_per_agent": 4, "filter_taps": 2, "n_layers": 3,
         "test_per_agent": 6}
FILTER = 2 * 2 * 8 * 8 * 68          # K products S @ Y of (8,8)x(8,68)
PERCEPTRON = 2 * 8 * 148 * 68        # (8,148) x (148,68)
TEST_LOSS = 2 * 8 * 6 * 16 * 4       # logits of 6 test rows per agent
GRAD_NORM = 4 * 8 * 4 * 16 * 4       # logits + gradient on 4 rows
THROUGH_M = 2 * 8 * 68 * 68          # gradient into W through M's W rows


def test_layer_flops():
    assert flops.layer_flops(SMOKE) == (17408, 161024)
    assert (FILTER, PERCEPTRON) == (17408, 161024)


def test_meta_step_flops_by_hand():
    forward = 3 * (FILTER + PERCEPTRON) + TEST_LOSS + 4 * GRAD_NORM
    backward = (3 * PERCEPTRON + 2 * (FILTER + THROUGH_M) + TEST_LOSS
                + 3 * GRAD_NORM)
    assert forward == 574208 and backward == 696576
    assert flops.meta_step_flops(SMOKE) == forward + backward == 1270784


def test_solve_flops_by_hand():
    per_layer = FILTER + PERCEPTRON + 2 * TEST_LOSS
    assert flops.solve_flops(SMOKE) == 3 * per_layer == 572160
    # at a smaller true cohort the count follows n, not the bucket
    assert flops.solve_flops(SMOKE, n=4) < flops.solve_flops(SMOKE)


def test_paper_size_counts():
    paper = {"n_agents": 100, "feature_dim": 512, "n_classes": 10,
             "batch_per_agent": 10, "filter_taps": 2, "n_layers": 10,
             "test_per_agent": 15}
    # d = 5130, din = 10350: about 0.26 TFLOP a meta-step, 0.11 a solve
    assert 2.6e11 < flops.meta_step_flops(paper) < 2.7e11
    assert 1.07e11 < flops.solve_flops(paper) < 1.09e11
