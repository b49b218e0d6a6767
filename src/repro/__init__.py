"""GossipGraD reproduction package root."""
