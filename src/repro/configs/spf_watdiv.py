"""The paper's own workload: WatDiv graph + query loads + SPF engine config.

Paper-faithful constants: LDF page size 50, |Omega| <= 30, four interfaces,
query loads 1-star/2-stars/3-stars/paths/union, up to 128 concurrent
clients.  ``scale=20_500`` reproduces the paper's ~10M-triple WatDiv
instance (the generator gives ~488 triples per scale unit: 10,018,031
distinct triples at seed 7); the CPU benchmarks default to ``scale=200``
(~100k triples) and scale linearly.
"""
from repro.core.engine import EngineConfig
from repro.rdf.watdiv import WatDivConfig
from repro.rdf.queries import QueryLoadConfig

FULL_GRAPH = WatDivConfig(scale=20_500)
BENCH_GRAPH = WatDivConfig(scale=200)
SMOKE_GRAPH = WatDivConfig(scale=20)
QUERY_LOADS = ("1-star", "2-stars", "3-stars", "paths", "union")
QUERIES_PER_LOAD = QueryLoadConfig(n_queries=50)
ENGINES = {i: EngineConfig(interface=i) for i in
           ("tpf", "brtpf", "spf", "endpoint")}
CLIENT_COUNTS = tuple(2 ** i for i in range(8))  # 1..128 concurrent clients
