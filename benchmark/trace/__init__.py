"""Device-trace reduction: ``.xplane.pb`` -> intervals -> metrics."""
