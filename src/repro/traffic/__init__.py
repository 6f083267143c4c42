"""Fleet traffic subsystem: open-loop arrival processes, per-server
queue/capacity stations, the discrete-event simulator that closes the
load->latency loop around the routing stack (SONAR vs SONAR-LB), and the
live request sources that replay the same arrival processes as online
serving traffic for the micro-batch front-end (repro.serving)."""
from repro.traffic.arrivals import (  # noqa: F401
    ARRIVAL_PROCESSES,
    diurnal_arrivals,
    flash_crowd_arrivals,
    merge_arrivals,
    mmpp_arrivals,
    poisson_arrivals,
    thinned_arrivals,
)
from repro.traffic.fleet import (  # noqa: F401
    ideal_platform,
    mega_fleet_index,
    mega_platform,
    replica_fleet,
    telemetry_palette,
    telemetry_template_map,
)
from repro.traffic.queueing import QueueConfig, ServerQueue  # noqa: F401
from repro.traffic.simulator import (  # noqa: F401
    FleetTrafficSim,
    Request,
    TrafficReport,
)
from repro.traffic.source import LiveRequest, request_schedule  # noqa: F401
