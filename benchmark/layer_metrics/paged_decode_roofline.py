"""flash_decode_paged's share of its HBM roofline (percent). Source: device trace and costs.py."""
from benchmark.readers import paged_decode_roofline as read  # noqa: F401
