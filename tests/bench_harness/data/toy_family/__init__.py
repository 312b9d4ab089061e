"""The modules ``../toy-family.json`` names: weights, counts and reference of
a made-up family, a few lines each, for ``test_config_names.py``."""
