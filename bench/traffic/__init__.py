"""Traffic generators (<generator>.py) and the mixes that parametrise them
(<mix>.json)."""
