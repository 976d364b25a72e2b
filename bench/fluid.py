"""Fluid capacity of the rack model under hot-rack traffic: the largest
arrival rate (tasks/slot) that a cluster of `num_servers` in racks of
`rack_size` can serve when a share `p_hot` of the tasks has all its
replicas in rack 0 and the rest are spread over the whole cluster.

Written from the model: rack 0 serves hot tasks at the local rate (its
servers hold the data or fetch it through the rack's own switch), every
other server serves them at the remote rate, and every server serves the
spread tasks at the local rate.  The hot traffic fills rack 0 first and
overflows to the remote servers; the capacity is the rate at which the
whole cluster is busy.  A cell's arrival rate is its load times this.
"""

from __future__ import annotations


def capacity(num_servers: int, rack_size: int, rates, p_hot: float) -> float:
    local, _, remote = (float(r) for r in rates)
    if p_hot <= 0.0:
        return float(num_servers * local)
    used_n = used_c = 0.0     # servers, and their hot service, already full
    for rate, n in ((local, rack_size), (remote, num_servers - rack_size)):
        lam = ((num_servers - used_n + used_c / rate)
               / (p_hot / rate + (1.0 - p_hot) / local))
        hot = p_hot * lam - used_c          # hot traffic landing in this pool
        if -1e-9 <= hot <= n * rate + 1e-9:
            return float(lam)
        used_n += n
        used_c += n * rate
    raise ValueError(f"no fluid regime for {num_servers} servers in racks "
                     f"of {rack_size} at p_hot {p_hot}")
