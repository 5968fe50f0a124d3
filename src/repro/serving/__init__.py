from .engine import BlockwiseExecutor, layer_refs
from .server import (CoInferenceServer, MultiTenantServeReport,
                     MultiTenantServer, OnlineServeReport, Request,
                     ServeReport, TenantModel, run_partitioned)

__all__ = ["BlockwiseExecutor", "layer_refs", "CoInferenceServer",
           "MultiTenantServeReport", "MultiTenantServer",
           "OnlineServeReport", "Request", "ServeReport", "TenantModel",
           "run_partitioned"]
