"""Host time of one forward of the layered numpy policy in the actor
processes (the residual block and both LayerNorms, actors/policy.py), in
microseconds: `policy_forward_us` on each `"train"` record is the mean over
the forwards all workers made since the record before; this is the mean over
the window's records. It bounds the rows a worker can deliver, which a
free-running learner does not wait for. Only a run whose workers step a
layered policy (`DDPGConfig.simba`) writes the key."""


def read(run):
    times = [r["policy_forward_us"] for r in run["window"] if "policy_forward_us" in r]
    return sum(times) / len(times) if times else None
