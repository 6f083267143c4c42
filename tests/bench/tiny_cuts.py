"""Tiny CPU cuts of the configurations and traffic mixes that came after
the tables of ``conftest.py`` (``TINY``, ``TINY_TRAFFIC``), in their form;
the repository's root ``conftest.py`` merges them in as that fixture
loads.

The pool's cut keeps 3,000 replicas and the configuration's down rack
(replicas 0 to 39, candidates of every template), so health decides
picks from the warm-up on."""

TINY = {
    "paper-pool-1m": ("tiny-pool", dict(n_replicas=3000)),
}
TINY_TRAFFIC = {
    "flush64": dict(clients=32, text_pool=400, warm_rounds=2),
}
