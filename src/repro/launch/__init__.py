from repro.launch.mesh import (
    auto_mesh,
    make_local_mesh,
    make_production_mesh,
    production_topology,
)
from repro.launch.offload_runtime import build_offload_engine, get_engine
