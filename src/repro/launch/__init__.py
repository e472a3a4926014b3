# launch: meshes, input specs, sharded steps, HLO cost counts, drivers.
from repro.launch import mesh, hlo_cost  # light, device-free

__all__ = ["mesh", "hlo_cost"]
