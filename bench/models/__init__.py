"""One module per dataset, named as the configuration's ``dataset.name``:
``twin(seed, n_train, n_test)``, the reference's ``init`` and ``forward``,
and ``layer_flops``.  A configuration on a new dataset adds its module."""
import importlib


def of(config: dict):
    """The module of the configuration's dataset."""
    return importlib.import_module(f"bench.models.{config['dataset']['name']}")
