"""Reader of the replicas' disagreement after the window."""


def consensus_spread(reading):
    """``train/step.py::replica_spread`` on the de-biased parameters:
    largest deviation of a replica from the replicas' mean over the
    parameters' scale.  Nothing on a single replica."""
    from stochastic_gradient_push_tpu.train.step import replica_spread

    job = reading.job
    if job.world < 2:
        return None
    spread = replica_spread(job.state, job.algorithm)
    return spread["max_spread"] / spread["param_scale"]
