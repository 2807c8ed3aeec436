"""The plain reference: MQTT topic-filter matching (`+` one level, `#` the
rest, `$`-topics hidden from root wildcards) as a dictionary trie. It shares
nothing with the program and is given only the seed's data."""


class Matcher:
    def __init__(self):
        self.root = {}
        self.count = 0

    def insert(self, flt, owner):
        node = self.root
        for level in flt.split("/"):
            node = node.setdefault(level, {})
        node.setdefault(None, []).append(owner)  # None: owners ending here
        self.count += 1

    def match(self, topic):
        """-> owners of every filter matching `topic`, one entry each."""
        levels = topic.split("/")
        out = []
        sys_topic = topic.startswith("$")
        frontier = [self.root]
        for depth, level in enumerate(levels):
            nxt = []
            for node in frontier:
                hide = sys_topic and depth == 0
                if "#" in node and not hide:
                    out += node["#"].get(None, ())
                if level in node:
                    nxt.append(node[level])
                if "+" in node and not hide:
                    nxt.append(node["+"])
            frontier = nxt
        for node in frontier:
            out += node.get(None, ())
            if "#" in node:  # `a/#` also matches `a`
                out += node["#"].get(None, ())
        return out
