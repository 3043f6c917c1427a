"""The benchmark of stochastic_gradient_push_tpu: one command, data-driven.

``python benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` (see ``benchmark/README.md``).  Everything here is the
yardstick: later PRs change the program and add files here, they do not
edit a file that is here.
"""
