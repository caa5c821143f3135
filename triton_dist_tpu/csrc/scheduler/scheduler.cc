// Task scheduler for the mega (fused decode step) runtime.
//
// TPU-native equivalent of the reference's scheduler
// (python/triton_dist/mega_triton_kernel/core/scheduler.py:40-95:
// round-robin / zig-zag static assignment of tasks to per-SM work queues)
// plus the dependency resolution the reference does in ModelBuilder
// (models/model_builder.py). C++ because it runs per model-(re)build on
// the host and the reference keeps its scheduling/graph machinery native
// (csrc/, SURVEY.md §2.1); exposed to Python via ctypes (no pybind11 in
// this image).
//
// Build: gcc -shared -fPIC -O2 -o libtdtsched.so scheduler.cc

#include <cstdint>
#include <algorithm>
#include <cstring>
#include <queue>
#include <vector>

extern "C" {

// Round-robin assignment of n_tasks to n_queues. out[i] = queue of task i.
void tdt_schedule_round_robin(int32_t n_tasks, int32_t n_queues,
                              int32_t* out) {
  for (int32_t i = 0; i < n_tasks; ++i) out[i] = i % n_queues;
}

// Zig-zag: 0,1,..,q-1,q-1,..,1,0,0,1,.. — balances queue tail lengths the
// way the reference's ZIG_ZAG policy does for uneven task costs.
void tdt_schedule_zigzag(int32_t n_tasks, int32_t n_queues, int32_t* out) {
  int32_t period = 2 * n_queues;
  for (int32_t i = 0; i < n_tasks; ++i) {
    int32_t r = i % period;
    out[i] = r < n_queues ? r : period - 1 - r;
  }
}

// Cost-aware list scheduling: assign each task (in order) to the queue
// with the least accumulated cost. costs may be null (unit costs).
void tdt_schedule_least_loaded(int32_t n_tasks, int32_t n_queues,
                               const int64_t* costs, int32_t* out) {
  std::vector<int64_t> load(n_queues, 0);
  for (int32_t i = 0; i < n_tasks; ++i) {
    int32_t best = 0;
    for (int32_t q = 1; q < n_queues; ++q)
      if (load[q] < load[best]) best = q;
    out[i] = best;
    load[best] += costs ? costs[i] : 1;
  }
}

// HEFT-style critical-path list scheduling: tasks are prioritized by
// upward rank (longest cost-weighted path to a sink) and placed on the
// queue giving the earliest dependency-respecting start time. Returns the
// resulting makespan (or -1 on a cycle); out[i] = queue of task i. The
// makespan doubles as a speed-of-light estimate for the fused step given
// n_queues-way parallel hardware.
int64_t tdt_schedule_critical_path(int32_t n_tasks, int32_t n_edges,
                                   const int32_t* edges, int32_t n_queues,
                                   const int64_t* costs, int32_t* out) {
  std::vector<std::vector<int32_t>> children(n_tasks), parents(n_tasks);
  std::vector<int32_t> outdeg(n_tasks, 0);
  for (int32_t e = 0; e < n_edges; ++e) {
    int32_t src = edges[2 * e], dst = edges[2 * e + 1];
    children[src].push_back(dst);
    parents[dst].push_back(src);
    outdeg[src]++;
  }
  auto cost = [&](int32_t i) -> int64_t { return costs ? costs[i] : 1; };
  // upward ranks via reverse topological order (Kahn on the transpose)
  std::vector<int64_t> rank(n_tasks, 0);
  std::vector<int32_t> od = outdeg;
  std::queue<int32_t> q;
  int32_t seen = 0;
  for (int32_t i = 0; i < n_tasks; ++i)
    if (od[i] == 0) q.push(i);
  while (!q.empty()) {
    int32_t t = q.front();
    q.pop();
    seen++;
    int64_t best = 0;
    for (int32_t c : children[t])
      if (rank[c] > best) best = rank[c];
    rank[t] = cost(t) + best;
    for (int32_t p : parents[t])
      if (--od[p] == 0) q.push(p);
  }
  if (seen != n_tasks) return -1;
  // priority order: descending rank, ties broken by topological
  // position — raw-id ties could schedule a zero-cost parent's child
  // first (rank equality), violating dependencies.
  std::vector<int32_t> topo(n_tasks), pos(n_tasks);
  {
    std::vector<int32_t> indeg(n_tasks, 0);
    for (int32_t i = 0; i < n_tasks; ++i)
      for (int32_t c2 : children[i]) indeg[c2]++;
    std::priority_queue<int32_t, std::vector<int32_t>,
                        std::greater<int32_t>> rq;
    for (int32_t i = 0; i < n_tasks; ++i)
      if (indeg[i] == 0) rq.push(i);
    int32_t n2 = 0;
    while (!rq.empty()) {
      int32_t t = rq.top();
      rq.pop();
      topo[n2] = t;
      pos[t] = n2++;
      for (int32_t c2 : children[t])
        if (--indeg[c2] == 0) rq.push(c2);
    }
  }
  std::vector<int32_t> order(n_tasks);
  for (int32_t i = 0; i < n_tasks; ++i) order[i] = i;
  std::sort(order.begin(), order.end(), [&](int32_t a, int32_t b) {
    if (rank[a] != rank[b]) return rank[a] > rank[b];
    return pos[a] < pos[b];
  });
  std::vector<int64_t> queue_free(n_queues, 0), finish(n_tasks, 0);
  int64_t makespan = 0;
  for (int32_t t : order) {
    int64_t ready = 0;
    for (int32_t p : parents[t])
      if (finish[p] > ready) ready = finish[p];
    int32_t best_q = 0;
    int64_t best_start = -1;
    for (int32_t qi = 0; qi < n_queues; ++qi) {
      int64_t start = queue_free[qi] > ready ? queue_free[qi] : ready;
      if (best_start < 0 || start < best_start) {
        best_start = start;
        best_q = qi;
      }
    }
    out[t] = best_q;
    finish[t] = best_start + cost(t);
    queue_free[best_q] = finish[t];
    if (finish[t] > makespan) makespan = finish[t];
  }
  return makespan;
}

// HEFT priority linearization: the order tdt_schedule_critical_path
// visits tasks in (descending upward rank, ties by topological
// position). It is itself a valid topological order (a parent's rank is
// >= any child's by at least its own cost; zero-cost ties fall back to
// topo position), so the mega executor can EMIT tasks in this order —
// which biases XLA's buffer-liveness and latency-hiding scheduling
// toward the critical path (what to compare: peak temp memory of
// topo- vs heft-emitted programs). Returns 0, or -1 on
// a cycle. out receives the task ids in priority order.
int32_t tdt_priority_order(int32_t n_tasks, int32_t n_edges,
                           const int32_t* edges, const int64_t* costs,
                           int32_t* out) {
  std::vector<std::vector<int32_t>> children(n_tasks), parents(n_tasks);
  std::vector<int32_t> outdeg(n_tasks, 0);
  for (int32_t e = 0; e < n_edges; ++e) {
    int32_t src = edges[2 * e], dst = edges[2 * e + 1];
    children[src].push_back(dst);
    parents[dst].push_back(src);
    outdeg[src]++;
  }
  auto cost = [&](int32_t i) -> int64_t { return costs ? costs[i] : 1; };
  std::vector<int64_t> rank(n_tasks, 0);
  std::vector<int32_t> od = outdeg;
  std::queue<int32_t> q;
  int32_t seen = 0;
  for (int32_t i = 0; i < n_tasks; ++i)
    if (od[i] == 0) q.push(i);
  while (!q.empty()) {
    int32_t t = q.front();
    q.pop();
    seen++;
    int64_t best = 0;
    for (int32_t c : children[t])
      if (rank[c] > best) best = rank[c];
    rank[t] = cost(t) + best;
    for (int32_t p : parents[t])
      if (--od[p] == 0) q.push(p);
  }
  if (seen != n_tasks) return -1;
  std::vector<int32_t> topo(n_tasks), pos(n_tasks);
  {
    std::vector<int32_t> indeg(n_tasks, 0);
    for (int32_t i = 0; i < n_tasks; ++i)
      for (int32_t c2 : children[i]) indeg[c2]++;
    std::priority_queue<int32_t, std::vector<int32_t>,
                        std::greater<int32_t>> rq;
    for (int32_t i = 0; i < n_tasks; ++i)
      if (indeg[i] == 0) rq.push(i);
    int32_t n2 = 0;
    while (!rq.empty()) {
      int32_t t = rq.top();
      rq.pop();
      topo[n2] = t;
      pos[t] = n2++;
      for (int32_t c2 : children[t])
        if (--indeg[c2] == 0) rq.push(c2);
    }
  }
  for (int32_t i = 0; i < n_tasks; ++i) out[i] = i;
  std::sort(out, out + n_tasks, [&](int32_t a, int32_t b) {
    if (rank[a] != rank[b]) return rank[a] > rank[b];
    return pos[a] < pos[b];
  });
  return 0;
}

// Kahn topological sort with stable tie-break by task id (the dependency
// resolution of the reference's ModelBuilder). edges: n_edges pairs
// (src, dst) meaning dst depends on src. Returns 0 on success, -1 on a
// cycle. out receives the execution order (task ids).
int32_t tdt_toposort(int32_t n_tasks, int32_t n_edges, const int32_t* edges,
                     int32_t* out) {
  std::vector<std::vector<int32_t>> adj(n_tasks);
  std::vector<int32_t> indeg(n_tasks, 0);
  for (int32_t e = 0; e < n_edges; ++e) {
    int32_t src = edges[2 * e], dst = edges[2 * e + 1];
    adj[src].push_back(dst);
    indeg[dst]++;
  }
  std::priority_queue<int32_t, std::vector<int32_t>,
                      std::greater<int32_t>> ready;
  for (int32_t i = 0; i < n_tasks; ++i)
    if (indeg[i] == 0) ready.push(i);
  int32_t n = 0;
  while (!ready.empty()) {
    int32_t t = ready.top();
    ready.pop();
    out[n++] = t;
    for (int32_t d : adj[t])
      if (--indeg[d] == 0) ready.push(d);
  }
  return n == n_tasks ? 0 : -1;
}

// Dependency-aware wavefront partition: tasks with equal depth (longest
// path from a source) share a wave — the analog of the reference's
// scoreboard-separated phases; waves become fusion groups for the jit
// executor. Returns the number of waves; out_wave[i] = wave of task i.
int32_t tdt_wavefronts(int32_t n_tasks, int32_t n_edges,
                       const int32_t* edges, int32_t* out_wave) {
  std::vector<std::vector<int32_t>> adj(n_tasks);
  std::vector<int32_t> indeg(n_tasks, 0);
  for (int32_t e = 0; e < n_edges; ++e) {
    adj[edges[2 * e]].push_back(edges[2 * e + 1]);
    indeg[edges[2 * e + 1]]++;
  }
  std::vector<int32_t> depth(n_tasks, 0);
  std::queue<int32_t> ready;
  for (int32_t i = 0; i < n_tasks; ++i)
    if (indeg[i] == 0) ready.push(i);
  int32_t max_depth = -1, seen = 0;
  while (!ready.empty()) {
    int32_t t = ready.front();
    ready.pop();
    seen++;
    if (depth[t] > max_depth) max_depth = depth[t];
    for (int32_t d : adj[t]) {
      if (depth[t] + 1 > depth[d]) depth[d] = depth[t] + 1;
      if (--indeg[d] == 0) ready.push(d);
    }
  }
  if (seen != n_tasks) return -1;
  std::memcpy(out_wave, depth.data(), n_tasks * sizeof(int32_t));
  return max_depth + 1;
}

}  // extern "C"
