"""Plain reference codes, one module per scheme: ``parity(data, cfg)``
maps (k, C) data chunks to the (m, C) parity the configuration's code
defines.  They share nothing with the store's own coding paths."""
