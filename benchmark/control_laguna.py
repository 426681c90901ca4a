"""The controls that tell Laguna's attention from what is left of it
without its mechanisms (the `laguna_xs_2` configuration's second control):

    python3 benchmark/control_laguna.py --workload <cell> --seeds 1,2

For each seed: the plain reference through the three checked steps, then
the same reference twice more, each time with one thing taken out —
`output_gate` false: the per-head gate left out (g = 1, W_g without a
gradient); `yarn_rope` false: the full-attention layers given the window
layers' rotary (the plain table at their theta, on the whole head, no
factor on cos and sin) — compared with the true reference by
`check.compare` under the cell's limits. Each must come out as not correct,
else the limits could not tell a gated head from an ungated one, or the two
rotary tables apart. The run itself is `control_latent.py`'s, given this
file's forms: no solver is built, and the benchmark never runs it. The exit
code is 0 when every substitute failed a limit on every seed."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import control_latent

FORMS = ("output_gate", "yarn_rope")


def main(argv=None):
    control_latent.FORMS = FORMS
    return control_latent.main(argv)


if __name__ == "__main__":
    sys.exit(main())
