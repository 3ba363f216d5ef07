/* MPI_Comm_spawn of a real executable from C:
 * the parent job spawns maxprocs OS processes running THIS binary
 * (argv marker selects the child role); the child's MPI_Init wires it
 * to the parent job through the dpm port plane (the PMIx parent-
 * nspace handshake) and MPI_Comm_get_parent recovers the
 * intercommunicator. Cross-job traffic then flows both ways.
 * References: ompi/mpi/c/comm_spawn.c.in, comm_get_parent.c.in,
 * ompi/dpm/dpm.c:108-170. */
#include <mpi.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

static int rank, size;

#define CHECK(cond, code)                                            \
    do {                                                             \
        if (!(cond)) {                                               \
            fprintf(stderr, "rank %d: check failed at line %d\n",    \
                    rank, __LINE__);                                 \
            MPI_Abort(MPI_COMM_WORLD, code);                         \
        }                                                            \
    } while (0)

static int child_main(void)
{
    MPI_Comm parent = MPI_COMM_NULL;
    CHECK(MPI_Comm_get_parent(&parent) == MPI_SUCCESS, 40);
    CHECK(parent != MPI_COMM_NULL, 41);
    int is_inter = 0;
    MPI_Comm_test_inter(parent, &is_inter);
    CHECK(is_inter, 42);
    int psize = -1;
    MPI_Comm_remote_size(parent, &psize);
    CHECK(psize >= 1, 43);

    /* child world is its own MPI_COMM_WORLD */
    int token = -1;
    if (rank == 0) {
        MPI_Recv(&token, 1, MPI_INT, 0, 3, parent, MPI_STATUS_IGNORE);
        CHECK(token == 777, 44);
        token = 888 + size;              /* child world size back */
        MPI_Send(&token, 1, MPI_INT, 0, 4, parent);
    }
    MPI_Barrier(MPI_COMM_WORLD);
    printf("OK spawned-child rank=%d/%d\n", rank, size);
    MPI_Comm_disconnect(&parent);
    MPI_Finalize();
    return 0;
}

int main(int argc, char **argv)
{
    MPI_Init(&argc, &argv);
    MPI_Comm_rank(MPI_COMM_WORLD, &rank);
    MPI_Comm_size(MPI_COMM_WORLD, &size);

    if (argc > 1 && strcmp(argv[1], "--child") == 0)
        return child_main();

    /* parent: no parent of its own */
    MPI_Comm parent = (MPI_Comm)99;
    CHECK(MPI_Comm_get_parent(&parent) == MPI_SUCCESS, 2);
    CHECK(parent == MPI_COMM_NULL, 3);

    char *child_argv[] = {"--child", NULL};
    MPI_Comm inter = MPI_COMM_NULL;
    int errcodes[2] = {-1, -1};
    CHECK(MPI_Comm_spawn(argv[0], child_argv, 2, MPI_INFO_NULL, 0,
                         MPI_COMM_WORLD, &inter, errcodes)
          == MPI_SUCCESS, 4);
    CHECK(inter != MPI_COMM_NULL, 5);
    CHECK(errcodes[0] == MPI_SUCCESS && errcodes[1] == MPI_SUCCESS, 6);
    int rsize = -1;
    MPI_Comm_remote_size(inter, &rsize);
    CHECK(rsize == 2, 7);

    if (rank == 0) {
        int token = 777;
        MPI_Send(&token, 1, MPI_INT, 0, 3, inter);
        MPI_Recv(&token, 1, MPI_INT, 0, 4, inter, MPI_STATUS_IGNORE);
        CHECK(token == 888 + 2, 8);
    }
    MPI_Barrier(MPI_COMM_WORLD);
    MPI_Comm_disconnect(&inter);
    printf("OK c25_spawn rank=%d/%d\n", rank, size);
    MPI_Finalize();
    return 0;
}
