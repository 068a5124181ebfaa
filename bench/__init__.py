"""On-chip benchmark of the discovery service (see README.md here)."""
