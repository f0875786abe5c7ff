"""The grouped-query LM's cell at toy size for the CPU tests: the toy model
of tests/lm_gqa_toy.py as a configuration file's dict, and a traffic mix
of a few short sessions with the same keys as
sessions64-ctx1k-64k-sum900k.json."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lm_gqa_toy import TOY  # noqa: E402

CONFIG = dict(TOY, pipeline="lm", job="serve_gqa", assumed={})

TRAFFIC = {
    "kind": "serve", "loop": "closed", "sessions": 6,
    "ctx_min": 3, "ctx_max": 40, "ctx_sum": 90, "ctx_sum_tolerance": 0.5,
    "prefill_chunk": 8, "bucket_edges": [1, 4, 8], "max_batch": 6,
    "queue_capacity": 12, "deadline_s": 30.0,
    "prefill_deadline_s": 600.0, "wedge_timeout_s": 600.0,
    "page": 4, "cache_tokens": 2400, "max_len": 320,
    "trace_after_steps": 2, "trace_steps": 2,
    "check_sessions": 4, "check_steps": 3, "check_steps_below": 6,
    "check_short_below": 6, "check_long_above": 20,
    "check_mid_below": 16,
    # the controls' ``truncate`` at the toy's scale: the last 4 positions
    "control_truncate": 4,
    "reference_blocks": {"q_block": 8, "head_group": 2, "key_round": 16,
                         "window_round": 8, "mlp_block": 16, "pad_to": 64,
                         "expert_group": 4},
    # float32 on both sides: round-off of two orders of summation, and no
    # routed expert differs but on a tie of that round-off
    "limits": {"logits_rel_rms": 1e-4, "logits_max_gap": 1e-3,
               "route_miss": 0.02, "choices_missing": 0.0},
}
