"""Analysis utilities: scan-aware HLO walker, roofline model, device setup."""
